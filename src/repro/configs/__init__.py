"""Architecture registry: the assigned configs + shapes."""

from repro.configs import (
    deepseek_v2_lite,
    grok_1_314b,
    llama4_scout_17b_a16e,
    minicpm_2b,
    olmo_1b,
    paligemma_3b,
    qwen3_14b,
    qwen3_1_7b,
    recurrentgemma_2b,
    rwkv6_3b,
    seamless_m4t_medium,
)
from repro.configs.base import (  # noqa: F401
    ALL_SHAPES,
    SHAPES_BY_NAME,
    ModelConfig,
    RunShape,
    assigned_cells,
    supports_shape,
)

_MODULES = (
    olmo_1b, qwen3_14b, qwen3_1_7b, minicpm_2b, recurrentgemma_2b,
    seamless_m4t_medium, paligemma_3b, rwkv6_3b, llama4_scout_17b_a16e,
    grok_1_314b, deepseek_v2_lite,
)

ARCHS = {m.ARCH: m for m in _MODULES}
ARCH_NAMES = tuple(ARCHS)


def get_config(name: str, tiny: bool = False) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {ARCH_NAMES}")
    mod = ARCHS[name]
    return mod.tiny() if tiny else mod.full()
