from paddlebox_tpu.parallel.mesh import make_mesh, data_axis_size
from paddlebox_tpu.parallel.layers import (
    vocab_parallel_embedding, column_parallel_linear, row_parallel_linear,
    pipeline_run,
    pipeline_train_step,
)
from paddlebox_tpu.parallel.moe import (
    moe_forward_local, moe_forward_sharded, naive_gating, route_top_k,
    routed_experts, top1_gating, top2_gating,
)
from paddlebox_tpu.parallel.ring_attention import (
    make_context_parallel_attention, reference_attention, ring_attention,
    ulysses_attention,
)

__all__ = [
    "make_mesh", "data_axis_size", "vocab_parallel_embedding",
    "column_parallel_linear", "row_parallel_linear", "pipeline_run",
    "pipeline_train_step",
    "moe_forward_local", "moe_forward_sharded", "naive_gating",
    "top1_gating", "top2_gating", "route_top_k", "routed_experts",
    "make_context_parallel_attention", "reference_attention",
    "ring_attention", "ulysses_attention",
]
