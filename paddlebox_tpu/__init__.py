"""paddlebox_tpu — a TPU-native large-scale sparse CTR training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of PaddleBox
(Baidu's PaddlePaddle fork for trillion-feature CTR training; reference
layout documented in SURVEY.md):

- ``paddlebox_tpu.data``     — streaming slot dataset / data-feed pipeline
  (reference: paddle/fluid/framework/data_feed.*, data_set.*).
- ``paddlebox_tpu.ps``       — the embedding parameter server: HBM-resident,
  mesh-sharded feature table with sparse optimizers
  (reference: paddle/fluid/framework/fleet/box_wrapper.*, heter_ps/*).
- ``paddlebox_tpu.ops``      — CTR op library: fused_seqpool_cvm family,
  rank_attention, batch_fc, … (reference: paddle/fluid/operators/*).
- ``paddlebox_tpu.models``   — ctr_dnn / DeepFM / Wide&Deep / DCN-v2 /
  AdsRank (PV ads ranking with rank attention) / MMoE (multi-task).
- ``paddlebox_tpu.train``    — trainer runtime: pass lifecycle, jit train
  step, checkpointing (reference: framework/boxps_trainer.cc, boxps_worker.cc).
- ``paddlebox_tpu.parallel`` — mesh construction, collectives, shardings
  (reference: fleet/nccl_wrapper.*, gloo_wrapper.*, collective ops).
- ``paddlebox_tpu.metrics``  — bucketed AUC / WuAUC / metric registry
  (reference: fleet/metrics.{h,cc}).
"""

__version__ = "0.1.0"

from paddlebox_tpu import config as config
from paddlebox_tpu.config import FLAGS as FLAGS
