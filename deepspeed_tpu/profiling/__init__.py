"""deepspeed_tpu.profiling: FLOPs/MFU profiling (reference ``profiling/``).

The reference counts MACs with module hooks; here XLA's own cost analysis and
jaxpr traversal provide exact compiled-program numbers (see
``flops_profiler.py``).
"""

from deepspeed_tpu.profiling.flops_profiler import (
    FlopsProfiler,
    ProfileResult,
    compiled_cost,
    flops_by_op,
    get_model_profile,
)
