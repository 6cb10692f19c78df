"""paddle.incubate.nn analog: MoE + fused transformer layers + functional."""
from . import functional  # noqa: F401
from .moe import (  # noqa: F401
    DroplessMoE, MoELayer, moe_aux_loss, moe_dropless, moe_ffn,
    moe_ffn_expert_choice, moe_route,
)
from .fused_transformer import (  # noqa: F401
    FusedMultiHeadAttention, FusedFeedForward,
)
