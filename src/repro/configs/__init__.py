"""Assigned architecture configs (one module per arch) + shape registry."""
from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, ArchConfig,
                                EncDecConfig, FrontendStub, InputShape,
                                MLAConfig, MambaConfig, MoEConfig, YarnScaling,
                                get_arch, get_smoke)

__all__ = ["ARCH_IDS", "INPUT_SHAPES", "ArchConfig", "EncDecConfig",
           "FrontendStub", "InputShape", "MLAConfig", "MambaConfig",
           "MoEConfig", "YarnScaling", "get_arch", "get_smoke"]
