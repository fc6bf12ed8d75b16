"""deepspeed_tpu.ops: the kernel layer (op_builder + csrc analog).

Ops are registered per-backend ('xla' plain jnp, 'pallas' TPU kernels) and
resolved through the registry at call time. The pallas package registers its
implementations on import — interpret mode off-TPU, so the CPU tests run the
same kernels. A kernel module that fails to import is an error, not a
warning: on a TPU it would silently hand every op to XLA.
"""

from deepspeed_tpu.ops.registry import available_impls, dispatch, op_report, register
from deepspeed_tpu.ops.attention import causal_attention, evoformer_attention
from deepspeed_tpu.ops.norms import layer_norm, rms_norm
from deepspeed_tpu.ops.rope import rope
from deepspeed_tpu.ops.quant import dequantize_int8, quantize_int8

from deepspeed_tpu.ops.pallas import register_all as _register_pallas

_register_pallas()
