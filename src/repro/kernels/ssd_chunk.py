"""Pallas kernel: Mamba2 SSD intra-chunk quadratic (zamba2 prefill hot spot).

Per chunk of Q tokens the SSD recurrence has a closed attention-like form::

    y[i] = Σ_{j<=i} (C_i·B_j) · exp(cum_i − cum_j) · dt_j · x_j

with per-head scalar decays.  This kernel evaluates one (batch, chunk)
program entirely in VMEM: a ``[Q, Q]`` score matmul on the MXU, a per-head
decay/causal mask on the VPU, and a ``[H, Q, Q] × [H, Q, P]`` batched matmul
back to the MXU.  The inter-chunk state scan stays in jnp (it is O(n_chunks)
and bandwidth-trivial).

Validated in interpret mode on CPU against ``ref.ssd_chunk_ref``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_on_platform


def _ssd_kernel(xh_ref, bm_ref, cm_ref, dt_ref, cum_ref, out_ref):
    """One (batch, chunk) program.

    xh_ref  [1, Q, H, P]   chunk inputs (post-conv, headed)
    bm_ref  [1, Q, N]      B projections
    cm_ref  [1, Q, N]      C projections
    dt_ref  [1, Q, H]      softplus'd step sizes
    cum_ref [1, Q, H]      cumulative log-decay within the chunk
    out_ref [1, Q, H, P]   intra-chunk contribution
    """
    xh = xh_ref[0].astype(jnp.float32)      # [Q, H, P]
    bm = bm_ref[0].astype(jnp.float32)      # [Q, N]
    cm = cm_ref[0].astype(jnp.float32)
    dt = dt_ref[0].astype(jnp.float32)      # [Q, H]
    cum = cum_ref[0].astype(jnp.float32)
    q, h, p = xh.shape

    # [Q, N] x [Q, N]^T -> [Q(i), Q(j)]  (MXU)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ii >= jj

    # per-head decayed weights + batched matmul back to tokens
    # w[h, i, j] = cb[i,j] * exp(cum[i,h]-cum[j,h]) * dt[j,h]   (j <= i)
    ci = cum.T[:, :, None]                   # [H, Q(i), 1]
    cj = cum.T[:, None, :]                   # [H, 1, Q(j)]
    decay = jnp.where(causal[None], jnp.exp(ci - cj), 0.0)      # [H,Q,Q]
    w = cb[None] * decay * dt.T[:, None, :]                     # [H,Q,Q]
    xh_h = xh.transpose(1, 0, 2)                                # [H,Q,P]
    # [H, Q, Q] x [H, Q, P] -> [H, Q, P]  (MXU, batched over H)
    y = jax.lax.dot_general(w, xh_h, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    out_ref[0] = y.transpose(1, 0, 2).astype(out_ref.dtype)


def _ssd_chunk_call(xh, bm, cm, dt, cum, *, interpret):
    bnc, q, h, p = xh.shape
    n = bm.shape[-1]
    return pl.pallas_call(
        _ssd_kernel,
        grid=(bnc,),
        in_specs=[
            pl.BlockSpec((1, q, h, p), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, h), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, h, p), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bnc, q, h, p), jnp.float32),
        interpret=interpret,
    )(xh, bm, cm, dt, cum)


@jax.jit
def ssd_chunk_pallas(xh, bm, cm, dt, cum):
    """Intra-chunk SSD.  ``xh [B, nc, Q, H, P]``, ``bm/cm [B, nc, Q, N]``,
    ``dt/cum [B, nc, Q, H]`` → ``[B, nc, Q, H, P]`` (fp32)."""
    b, nc, q, h, p = xh.shape
    flat = lambda t: t.reshape(b * nc, *t.shape[2:])
    out = pallas_on_platform(_ssd_chunk_call, flat(xh), flat(bm), flat(cm),
                             flat(dt), flat(cum))
    return out.reshape(b, nc, q, h, p)
