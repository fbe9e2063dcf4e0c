from paddlebox_tpu.ops.seqpool_cvm import (
    fused_seqpool_cvm, fused_seqpool_cvm_with_conv, fused_seqpool_concat,
)
from paddlebox_tpu.ops.pallas_kernels import (
    fused_embed_pool_cvm, segment_gather_mxu, segment_sum_mxu,
)
from paddlebox_tpu.ops.pallas_ctr import (
    fused_batch_fc, fused_cross_norm_hadamard, fused_rank_attention,
)
from paddlebox_tpu.ops.cvm import cvm, cvm_grad_passthrough
from paddlebox_tpu.ops.rank_attention import (rank_attention,
                                              rank_attention2)
from paddlebox_tpu.ops.batch_fc import batch_fc
from paddlebox_tpu.ops.shuffle_batch import shuffle_batch, unshuffle_batch
from paddlebox_tpu.ops.partial_ops import partial_concat, partial_sum
from paddlebox_tpu.ops.data_norm import (
    DataNormSummary, data_norm, data_norm_update, init_data_norm_summary,
)
from paddlebox_tpu.ops.cross_norm import (
    cross_norm_hadamard, cross_norm_update, init_cross_norm_summary,
)
from paddlebox_tpu.ops.scaled_fc import scaled_fc, scaled_int8fc
from paddlebox_tpu.ops.seqpool_variants import (
    fused_seqpool_cvm_with_diff_thres, fused_seqpool_cvm_tradew,
    fused_seqpool_cvm_with_credit, fused_seqpool_cvm_with_pcoc,
)
from paddlebox_tpu.ops.seq_tensor import fused_seq_tensor
from paddlebox_tpu.ops.ssd import ssd_scan
from paddlebox_tpu.ops.causal_attention import causal_gqa_attention

__all__ = [
    "fused_seqpool_cvm", "fused_seqpool_cvm_with_conv",
    "fused_seqpool_concat", "cvm", "cvm_grad_passthrough", "rank_attention",
    "rank_attention2",
    "batch_fc", "shuffle_batch", "unshuffle_batch", "partial_concat",
    "partial_sum", "DataNormSummary", "data_norm", "data_norm_update",
    "init_data_norm_summary", "cross_norm_hadamard", "cross_norm_update",
    "init_cross_norm_summary", "scaled_fc", "scaled_int8fc",
    "fused_seqpool_cvm_with_diff_thres", "fused_seqpool_cvm_tradew",
    "fused_seqpool_cvm_with_credit", "fused_seqpool_cvm_with_pcoc",
    "fused_seq_tensor", "fused_embed_pool_cvm", "segment_gather_mxu",
    "segment_sum_mxu", "fused_rank_attention", "fused_batch_fc",
    "fused_cross_norm_hadamard", "ssd_scan", "causal_gqa_attention",
]
