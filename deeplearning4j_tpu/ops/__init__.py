"""Tensor/op layer — the ND4J + libnd4j role, collapsed.

The reference's L4 (INDArray/op classes) + L2 (libnd4j kernels) layers become:
jax.Array + a named op catalog lowering to XLA. Importing this package
populates the global op registry.
"""

from deeplearning4j_tpu.ops.registry import registry, op, exec_op, OpRegistry
from deeplearning4j_tpu.ops import nn_ops, activations, losses, random, compression, weight_init
# declarable-op catalog breadth (each module registers its family + a
# numpy-oracle validation case per op — the OpValidation ratchet)
from deeplearning4j_tpu.ops import (
    transforms, reductions, shape_ops, scatter, linalg_ops, bitwise,
    image_ops, misc_ops, validation,
)
from deeplearning4j_tpu.ops.activations import get_activation, ACTIVATIONS
from deeplearning4j_tpu.ops.losses import get_loss, LOSSES
from deeplearning4j_tpu.ops.weight_init import init_weights

# Install the Pallas platform helpers (the cuDNN-helper-registration analog:
# the reference registers platform overrides at library load — libnd4j
# OpRegistrator static init). Deferred import keeps pallas optional.
from deeplearning4j_tpu.ops import tuning
from deeplearning4j_tpu.ops.pallas_attention import register_platform_attention
from deeplearning4j_tpu.ops.pallas_grouped import register_platform_grouped
from deeplearning4j_tpu.ops.pallas_retention import register_platform_retention
from deeplearning4j_tpu.ops.pallas_matmul import register_platform_fused_matmul
from deeplearning4j_tpu.ops.pallas_layernorm import (
    register_platform_fused_layernorm)
from deeplearning4j_tpu.ops.pallas_updater import (
    register_platform_fused_updater)
from deeplearning4j_tpu.ops.quantized import register_platform_quantized

register_platform_attention()
register_platform_grouped()
register_platform_retention()
register_platform_fused_matmul()
register_platform_fused_layernorm()
register_platform_fused_updater()
register_platform_quantized()

__all__ = [
    "registry", "op", "exec_op", "OpRegistry", "tuning",
    "nn_ops", "activations", "losses", "random", "compression", "weight_init",
    "get_activation", "ACTIVATIONS", "get_loss", "LOSSES", "init_weights",
]
