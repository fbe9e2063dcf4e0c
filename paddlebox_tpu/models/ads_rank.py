"""AdsRank — PV (page-view) ads ranking model with rank attention.

The production BoxPS pattern this mirrors: PV-merged batches flatten each
search result page's ads into instances with a ``rank_offset`` matrix
(PaddleBoxDataFeed::GetRankOffset, data_feed.cu:1319), and the net mixes
per-ad features with a per-(own-rank, other-rank) attention over co-shown
ads (``rank_attention`` op, operators/rank_attention_op.*) plus slot-wise
``batch_fc`` towers (operators/batch_fc_op.*). This module is the model
half; paddlebox_tpu/data/pv.py builds the batches.

The optional towers exercise the full device-side CTR op family
(ISSUE 13 — chip_smoke's kernels phase runs with all three on):

- ``slot_fc``: a per-slot ``batch_fc`` projection over the pooled
  embeddings (the reference's slot-wise tower, batch_fc_op default
  mode — [S, B, D] × [S, D, D] + [S, D]).
- ``cross_norm``: a ``cross_norm_hadamard`` block over the
  (projection, attention) pair — the [a, b, a⊙b, a·b] normalized
  cross features (cross_norm_hadamard_op, one field of width
  ``d_model``). The caller owns the ``DataNormSummary`` (pass it as
  ``cross_summary``; update it outside the grad with
  ``ops.cross_norm_update``, the data_norm summary-training pattern).
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from paddlebox_tpu.ops.batch_fc import batch_fc
from paddlebox_tpu.ops.cross_norm import cross_norm_hadamard
from paddlebox_tpu.ops.data_norm import DataNormSummary
from paddlebox_tpu.ops.rank_attention import rank_attention


class AdsRank(nn.Module):
    """pooled [B, S, D] + dense [B, Dd] + rank_offset [B, 1+2K] → logits [B].

    d_model: per-ad projection width fed to rank attention.
    max_rank: K, max co-shown ads attended per ad (must match the
      PvBatchBuilder's max_rank).
    slot_fc: per-slot batch_fc tower over the pooled embeddings.
    cross_norm: normalized hadamard-cross block over (proj, attention)
      — requires ``cross_summary`` at call time.
    """

    d_model: int = 64
    max_rank: int = 3
    hidden: Sequence[int] = (128, 64)
    compute_dtype: jnp.dtype = jnp.bfloat16
    slot_fc: bool = False
    cross_norm: bool = False

    @nn.compact
    def __call__(self, pooled: jax.Array, dense: jax.Array,
                 rank_offset: jax.Array,
                 cross_summary: Optional[DataNormSummary] = None
                 ) -> jax.Array:
        b, s, d = pooled.shape
        if self.slot_fc:
            w = self.param("slot_fc_w", nn.initializers.normal(0.02),
                           (s, d, d))
            bias = self.param("slot_fc_b", nn.initializers.zeros, (s, d))
            pooled = nn.relu(
                batch_fc(pooled.swapaxes(0, 1), w, bias)).swapaxes(0, 1)
        feats = jnp.concatenate(
            [pooled.reshape(b, s * d), dense], axis=1)
        proj = nn.Dense(self.d_model, dtype=self.compute_dtype,
                        name="ad_proj")(feats).astype(jnp.float32)

        # per-(own-rank, co-rank) attention parameter blocks
        rank_param = self.param(
            "rank_param", nn.initializers.normal(0.02),
            (self.max_rank * self.max_rank, self.d_model, self.d_model))
        ra = rank_attention(proj, rank_offset, rank_param,
                            max_rank=self.max_rank, enable_input_bp=True)

        h = jnp.concatenate([proj, ra], axis=1)
        if self.cross_norm:
            if cross_summary is None:
                raise ValueError(
                    "AdsRank(cross_norm=True) needs a cross_summary "
                    "(ops.init_cross_norm_summary(1, d_model))")
            cx = cross_norm_hadamard(h, cross_summary, 1, self.d_model)
            h = jnp.concatenate([h, cx], axis=1)
        for i, w in enumerate(self.hidden):
            h = nn.relu(nn.Dense(w, dtype=self.compute_dtype,
                                 name=f"mlp_{i}")(h).astype(jnp.float32))
        return nn.Dense(1, dtype=jnp.float32, name="head")(h)[:, 0]
