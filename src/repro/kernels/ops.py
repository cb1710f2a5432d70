"""Jitted public wrappers around the Pallas kernels.

Handle padding to tile multiples and layout conversion from the runtime's
token-major KV to the kernels' head-major layout.  The kernels run compiled
on a TPU and interpreted on the CPU (:mod:`repro.kernels.platform`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gather_attention import gather_attention_pallas
from repro.kernels.lowrank_score import lowrank_group_scores_pallas

NEG = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("group_size", "block_n"))
def lowrank_group_scores(q_lr, k_lr, valid_len, *, group_size: int,
                         block_n: int | None = None):
    """``q_lr [B,H,r], k_lr [B,N,r], valid_len [B]`` → group scores
    ``[B, ceil(N/G)]`` (padding groups scored NEG).  The default tile holds
    128 groups, the TPU's lane width."""
    b, n, r = k_lr.shape
    block_n = min(block_n or 128 * group_size, _round_up(n, group_size))
    block_n = _round_up(block_n, group_size)
    n_pad = _round_up(n, block_n)
    if n_pad != n:
        k_lr = jnp.pad(k_lr, ((0, 0), (0, n_pad - n), (0, 0)))
    out = lowrank_group_scores_pallas(
        q_lr, k_lr, valid_len, group_size=group_size, block_n=block_n)
    return out[:, : -((n_pad - n) // group_size) or None] if n_pad != n else out


@functools.partial(jax.jit, static_argnames=("block_t",))
def gather_attention(q, k, v, mask, *, block_t: int = 256):
    """Flash-decode over gathered KV.

    ``q [B,H,d]``, ``k/v [B,S,H_kv,d]`` (token-major, as the KV manager
    produces), ``mask [B,S]`` → ``[B,H,d]``.
    """
    b, s, hk, d = k.shape
    k = k.transpose(0, 2, 1, 3)  # head-major for the kernel
    v = v.transpose(0, 2, 1, 3)
    block = min(block_t, _round_up(s, 8))
    s_pad = _round_up(s, block)
    if s_pad != s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, s_pad - s)))
    return gather_attention_pallas(q, k, v, mask, block_t=block)
