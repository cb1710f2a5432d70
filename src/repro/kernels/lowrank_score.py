"""Pallas kernel: fused low-rank scoring + group reduce-max (KVSwap Eq. 1).

The decode-time prediction hot-spot: ``(Q·A) K_lr^T`` summed over heads and
max-reduced within groups of G.  On TPU this streams ``K_lr`` HBM→VMEM in
token tiles of ``block_n`` while the tiny ``Q_lr`` stays VMEM-resident.  Each
tile is read as G strided row sets (member g of every group), each scored by
one MXU matmul ``[H, r] × [r, T/G]``, summed over heads on the VPU and folded
into a running max, so the group scores come out lane-dense ``[1, T/G]``
without an in-kernel reshape.  The valid token count per row arrives as a
scalar-prefetch operand in SMEM.

Validated in interpret mode on CPU against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_on_platform

NEG = -1e30


def _score_kernel(valid_ref, qlr_ref, klr_ref, out_ref, *, block_n: int, group_size: int):
    """One (batch, token-tile) program.

    valid_ref[B]        — SMEM: valid token count per batch row
    qlr_ref  [H, r]     — VMEM-resident low-rank queries for this batch row
    klr_ref  [T, r]     — current K_lr token tile
    out_ref  [1, T / G] — group scores for this tile
    """
    i, j = pl.program_id(0), pl.program_id(1)
    g = group_size
    n_groups = block_n // g
    qlr = qlr_ref[...].astype(jnp.float32)                      # [H, r]
    grp = jax.lax.broadcasted_iota(jnp.int32, (1, n_groups), 1)
    first = j * block_n + grp * g                               # [1, T/G] token of member 0
    best = None
    for m in range(g):
        k_m = klr_ref[pl.ds(m, n_groups, stride=g), :].astype(jnp.float32)  # [T/G, r]
        # [H, r] x [T/G, r]^T -> [H, T/G]  (MXU), head aggregation -> [1, T/G]
        s = jax.lax.dot_general(qlr, k_m, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(first + m < valid_ref[i], s.sum(axis=0, keepdims=True), NEG)
        best = s if best is None else jnp.maximum(best, s)
    out_ref[...] = best


def _lowrank_scores_call(valid_len, q_lr, k_lr, *, group_size, block_n, interpret):
    b, h, r = q_lr.shape
    n = k_lr.shape[1]
    grid = (b, n // block_n)
    kernel = functools.partial(_score_kernel, block_n=block_n, group_size=group_size)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, h, r), lambda i, j, vl: (i, 0, 0)),
                pl.BlockSpec((None, block_n, r), lambda i, j, vl: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, block_n // group_size),
                                   lambda i, j, vl: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, n // group_size), jnp.float32),
        interpret=interpret,
    )(valid_len, q_lr, k_lr)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("group_size", "block_n"))
def lowrank_group_scores_pallas(
    q_lr: jax.Array,       # [B, H, r]
    k_lr: jax.Array,       # [B, N, r]  (N multiple of block_n)
    valid_len: jax.Array,  # [B] int32
    *,
    group_size: int,
    block_n: int,
) -> jax.Array:
    """Group scores ``[B, N // G]``.  On TPU ``block_n`` must be ``N`` or a
    multiple of ``128·G`` (lane-dense output tiles)."""
    n = k_lr.shape[1]
    if n % block_n or block_n % group_size:
        raise ValueError(f"N={n} must tile by block_n={block_n}, "
                         f"block_n by G={group_size}")
    call = functools.partial(_lowrank_scores_call, group_size=group_size,
                             block_n=block_n)
    return pallas_on_platform(call, valid_len.astype(jnp.int32), q_lr, k_lr)
