"""Architecture registry: one module per assigned architecture.

``get_config(name)`` returns the full config; ``reduced(cfg)`` builds the
smoke-test variant.  ``ALL_ARCHS`` lists the ten assigned architectures; the registry also
holds ``deepseek-v2-lite``, the benchmark's MLA + MoE configuration.
"""
from repro.configs.base import (  # noqa: F401
    ModelConfig,
    REGISTRY,
    SHAPES,
    ShapeSpec,
    StageSpec,
    get_config,
    reduced,
    shape_applicable,
)

# Register every architecture (import order = presentation order).
from repro.configs import xlstm_350m  # noqa: F401
from repro.configs import musicgen_large  # noqa: F401
from repro.configs import smollm_360m  # noqa: F401
from repro.configs import gemma2_9b  # noqa: F401
from repro.configs import minitron_4b  # noqa: F401
from repro.configs import starcoder2_3b  # noqa: F401
from repro.configs import deepseek_v2_236b  # noqa: F401
from repro.configs import deepseek_v2_lite  # noqa: F401
from repro.configs import kimi_k2_1t  # noqa: F401
from repro.configs import pixtral_12b  # noqa: F401
from repro.configs import jamba_52b  # noqa: F401

ALL_ARCHS = [
    "xlstm-350m",
    "musicgen-large",
    "smollm-360m",
    "gemma2-9b",
    "minitron-4b",
    "starcoder2-3b",
    "deepseek-v2-236b",
    "kimi-k2-1t-a32b",
    "pixtral-12b",
    "jamba-v0.1-52b",
]
