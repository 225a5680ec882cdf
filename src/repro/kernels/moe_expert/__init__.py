from repro.kernels.moe_expert.ops import (  # noqa: F401
    moe_expert_down,
    moe_expert_gated,
)
