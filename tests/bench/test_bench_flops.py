"""The benchmark's FLOPs count and peaks table."""
import pytest

from bench_tiny import harness  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import flops, models

LLAMA = models.load({})


def _cfg(layers):
    return dict(hidden_size=960, intermediate_size=2560,
                num_attention_heads=15, num_key_value_heads=5,
                num_hidden_layers=layers, vocab_size=49152)


@pytest.mark.parametrize("layers", [32, 8])
def test_flops_per_token_is_the_hand_count(layers):
    # per layer: q and o are 960 x 960 (15 heads of 64), k and v are
    # 960 x 320 (5 heads of 64), the MLP is three 960 x 2560 matrices
    per_layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    head = 49152 * 960                       # tied: counted once
    attn = 12 * layers * 15 * 64 * 256       # scores and values at S=256
    want = 6 * (layers * per_layer + head) + attn
    assert LLAMA.train_flops_per_token(_cfg(layers), 256) == want
    if layers == 32:
        # the 32-layer count: 361,821,120 parameters less 62,400 in norms
        assert LLAMA.matmul_params(_cfg(32)) == 361_821_120 - 62_400
        assert want == 2_264_924_160
    else:
        assert want == 778_567_680


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
