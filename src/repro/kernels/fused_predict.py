"""Fused decode-time prediction with the Pallas scoring kernel (Eq. 1).

Pallas edition of :func:`repro.core.predictor.fused_predict`: the low-rank
query projection feeds :func:`repro.kernels.ops.lowrank_group_scores` (the
``lowrank_score.py`` kernel — fused score + head aggregation + group
reduce-max streaming ``K_lr`` HBM→VMEM once), then top-M selection — all
under a single jit, so the engine's per-layer prediction is one dispatch and
one host pull of ``(ids, mask)``.

Selected by ``EngineConfig.use_pallas``; both the host-gather and the
device-resident decode paths route through the same implementation for a
given config, which is what keeps their decoded tokens bit-identical.
"""

from __future__ import annotations

import functools

import jax

from repro.core.predictor import lowrank_queries_per_head, select_groups
from repro.kernels.ops import lowrank_group_scores


@functools.partial(jax.jit, static_argnames=("group_size", "n_select"))
def fused_predict_pallas(
    q: jax.Array,                 # [B, H, d] — fully-normed, RoPE'd query
    per_head_a: jax.Array,        # [H_k, d, r] — adapter.per_head
    k_lr: jax.Array,              # [B, N, r] (N a multiple of G)
    valid_len: jax.Array,         # [B] int32 valid token count
    *,
    group_size: int,
    n_select: int,
) -> tuple[jax.Array, jax.Array]:
    """Returns device ``(group_ids [B, M], mask [B, M])``."""
    q_lr = lowrank_queries_per_head(q, per_head_a)
    gs = lowrank_group_scores(q_lr, k_lr, valid_len, group_size=group_size)
    return select_groups(gs, n_select)
