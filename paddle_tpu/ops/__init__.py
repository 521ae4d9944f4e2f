"""paddle_tpu.ops — TPU kernels (Pallas + lax): the counterpart of the
reference's operators/fused/ tier, built for the MXU instead of CUDA."""
from .attention import (  # noqa: F401
    blockwise_attention,
    dot_product_attention,
    ring_attention,
    set_attention_impl,
    set_ring_context,
    xla_attention,
)
from .fused import fused_adam_step, fused_layer_norm, fused_softmax_bias  # noqa: F401
from . import remat_policy, tier_policy  # noqa: F401
