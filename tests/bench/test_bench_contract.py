"""``BENCHMARK.json`` and the files it names hold together, and the
command refuses to run without a chip."""
import json
import math
import re

import pytest

from bench_tiny import ROOT, harness

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CHIP = ROOT / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"_dim$|_rank$|_size$|intermediate|head|expan|"
                   r"experts_per_tok|latent|proj")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip", "tests/bench"]
    for word in SPEC["command"][1:]:
        assert any(word.startswith(p + "/") for p in SPEC["paths"]), word


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for entry in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_every_cell_names_an_existing_configuration_and_traffic():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert (CHIP / "limits" / f"{w['name']}.json").is_file()
        assert w["chips"] in (1, 4)
    assert {w["config"] for w in SPEC["workloads"]} == set(configs)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("benchmarks/chip/configs/")
        for key in c["reduced"]:
            assert key in cfg and not WIDTH.search(key), key


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        target = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in target.get("workloads", CELLS), (m, cell)


def test_bounds_and_run_length_fit_the_check():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    # a full check of 24 cells: 2 + 14 x 24 runs of run_seconds + 60 s,
    # 2 x 90 s of compile a cell, 1200 s spare, in 43200 s
    rs = SPEC["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, math.floor(len(CELLS) / 2))


@pytest.mark.parametrize("cell", CELLS)
def test_cells_load(cell):
    c = harness.load_cell(cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "tokens_per_s"}
    assert c.per_layer and set(c.limits) == {"loss_gap", "update_gap",
                                             "change_gap"}


def test_command_exits_nonzero_without_a_tpu_and_prints_no_result(capsys):
    import jax

    from benchmarks.chip import run

    assert jax.devices()[0].platform != "tpu"
    assert run.main(["--workload", CELLS[0], "--seed", "3",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_benchmark_files_pass_the_repo_lint():
    from repro.analysis import lint_paths

    findings = lint_paths([str(CHIP), str(ROOT / "tests" / "bench")])
    assert not findings, [f.format() for f in findings]
