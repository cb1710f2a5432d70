"""Pallas kernel: flash-decode attention over gathered KV groups.

This is the attention the KVSwap runtime hands its heterogeneous KV view to
(reuse-buffer slots + freshly loaded groups + rolling buffer, flattened by
the mapping table into ``[B, H_kv, S_sel, d]`` + a validity mask).  One query
token per sequence, online-softmax accumulation across ``S_sel`` tiles so the
selected KV streams through VMEM exactly once.

Layout note: KV comes in head-major ``[B, H_kv, S, d]`` so each (kv-head,
token-tile) block is a contiguous ``[T, d]`` MXU operand, and the queries
come grouped by the kv head they share, ``[B, H_kv, H/H_kv, d]`` — the
wrapper in ops.py converts both from the runtime's layout.  One program
handles one (row, kv head, tile), so every in-kernel operand is 2-D.

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


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref,
                 m_scr, l_scr, acc_scr, *, n_tiles: int):
    """One (batch, kv-head, token-tile) program; scratch carries the online
    softmax across tiles.

    q_ref   [rep, d]  the query heads that share this kv head
    k_ref   [T, d]
    v_ref   [T, d]
    mask_ref[1, T]    int32; nonzero = valid
    out_ref [rep, d]
    scratch: m [rep, 1], l [rep, 1], acc [rep, d]  (fp32)
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)               # [rep, d]
    k = k_ref[...].astype(jnp.float32)               # [T, d]
    v = v_ref[...].astype(jnp.float32)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    # [rep, d] x [T, d]^T -> [rep, T]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    valid = mask_ref[...] != 0                       # [1, T]
    s = jnp.where(valid, s, NEG)

    m_prev = m_scr[...]                              # [rep, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    # zero out masked columns (exp(NEG - NEG) = 1 on a fully masked tile)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)    # [rep, T]
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    # [rep, T] x [T, d] -> [rep, d]
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new

    @pl.when(j == n_tiles - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        out_ref[...] = (acc_scr[...] / denom).astype(out_ref.dtype)


def _gather_attention_call(q, k, v, mask, *, block_t, interpret):
    b, hk, rep, d = q.shape
    s = k.shape[2]
    n_tiles = s // block_t
    kernel = functools.partial(_attn_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(b, hk, n_tiles),
        in_specs=[
            pl.BlockSpec((None, None, rep, d), lambda i, h, j: (i, h, 0, 0)),
            pl.BlockSpec((None, None, block_t, d), lambda i, h, j: (i, h, j, 0)),
            pl.BlockSpec((None, None, block_t, d), lambda i, h, j: (i, h, j, 0)),
            pl.BlockSpec((None, 1, block_t), lambda i, h, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, d), lambda i, h, j: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, rep, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask)


@functools.partial(jax.jit, static_argnames=("block_t",))
def gather_attention_pallas(
    q: jax.Array,     # [B, H, d]
    k: jax.Array,     # [B, H_kv, S, d]
    v: jax.Array,     # [B, H_kv, S, d]
    mask: jax.Array,  # [B, S] bool
    *,
    block_t: int,
) -> jax.Array:
    """``[B, H, d]``.  On TPU ``block_t`` must be ``S`` or a multiple of 128."""
    b, h, d = q.shape
    hk, s = k.shape[1], k.shape[2]
    if s % block_t:
        raise ValueError(f"S={s} must tile by block_t={block_t}")
    # query head h attends kv head h // rep (the repeat_kv convention)
    qg = q.reshape(b, hk, h // hk, d)
    call = functools.partial(_gather_attention_call, block_t=block_t)
    out = pallas_on_platform(call, qg, k, v, mask.astype(jnp.int32)[:, None, :])
    return out.reshape(b, h, d)
