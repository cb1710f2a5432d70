"""shard_map sequence-parallel KVSwap decode (DESIGN.md §2, TPU-native).

For ``long_500k``-class workloads the KV cache is sharded along the sequence
axis.  The GSPMD path (serving.decode + cache_pspecs) lets XLA pick the
collectives; this module is the *explicit* formulation — each shard:

1. scores its **local** ``K_lr`` slice against the (replicated) low-rank
   query (Eq. 1, head-summed),
2. selects its local top-``M/n_shards`` groups (per-shard quota — the
   distributed analogue of the paper's top-M; quota selection ≡ global top-M
   whenever the global winners spread ≤ quota per shard, and is otherwise a
   documented approximation),
3. computes a **partial flash-decode** over its selected tokens:
   ``(m_i, l_i, o_i)`` = (local max-logit, local normalizer, local output),
4. combines across shards with the flash-decoding identity::

       m = max_i m_i;   w_i = l_i · exp(m_i − m);   o = Σ w_i o_i / Σ w_i

Only the [B, H]-sized partials and one [B, H, d] output cross ICI —
independent of context length.  The new token (self) is attended by the
last shard (it owns the append position).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG = -1e30


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _local_partial(q, q_lr, k_lr, k, v, k_new, v_new, start, length,
                   *, group_size, quota, n_kv_heads, axis, n_shards):
    """Per-shard body.  Shapes are LOCAL (seq axis divided by n_shards).

    q, q_lr, k_new, v_new replicated; k_lr [B, n_loc, r]; k/v [B, n_loc, Hk, d].
    ``start`` = global offset of this shard's slice.
    Returns (m [B,H], l [B,H], o [B,H,d]) partials.
    """
    b, h, d = q.shape
    g = group_size
    n_loc = k_lr.shape[1]
    n_groups = n_loc // g

    scores = jnp.einsum("bhr,bnr->bn", q_lr, k_lr)            # [B, n_loc]
    pos = start + jnp.arange(n_loc)
    scores = jnp.where((pos < length)[None, :], scores, NEG)
    gsc = scores[:, : n_groups * g].reshape(b, n_groups, g).max(axis=-1)
    m_sel = min(quota, n_groups)
    top_sc, gids = jax.lax.top_k(gsc, m_sel)                  # local quota
    sel_valid = top_sc > NEG / 2

    tok_idx = (gids[..., None] * g + jnp.arange(g)[None, None, :]).reshape(b, -1)
    k_sel = jnp.take_along_axis(k, tok_idx[..., None, None], axis=1)  # [B,mG,Hk,d]
    v_sel = jnp.take_along_axis(v, tok_idx[..., None, None], axis=1)
    mask = ((start + tok_idx) < length) & jnp.repeat(sel_valid, g, axis=-1)

    # last shard also attends the new token (it owns the append position)
    idx = jax.lax.axis_index(axis)
    is_last = idx == n_shards - 1
    k_sel = jnp.concatenate([k_sel, k_new[:, None]], axis=1)
    v_sel = jnp.concatenate([v_sel, v_new[:, None]], axis=1)
    self_mask = jnp.broadcast_to(is_last, (b, 1))
    mask = jnp.concatenate([mask, self_mask], axis=1)

    hk = k_sel.shape[2]
    rep = h // hk
    kq = jnp.repeat(k_sel, rep, axis=2)
    vq = jnp.repeat(v_sel, rep, axis=2)
    s = jnp.einsum("bhd,bnhd->bhn", q, kq) / jnp.sqrt(d).astype(q.dtype)
    s = jnp.where(mask[:, None, :], s.astype(jnp.float32), NEG)
    m_i = s.max(axis=-1)                                      # [B,H]
    p = jnp.where(mask[:, None, :], jnp.exp(s - m_i[..., None]), 0.0)
    l_i = p.sum(axis=-1)
    o_i = jnp.einsum("bhn,bnhd->bhd", p.astype(q.dtype), vq).astype(jnp.float32)
    # normalize lazily at combine; guard all-masked shards
    safe_l = jnp.maximum(l_i, 1e-30)
    return m_i, l_i, o_i / safe_l[..., None]


def make_seqshard_decode_attn(mesh, *, axis: str = "data", group_size: int = 4,
                              n_select: int = 100, n_kv_heads: int):
    """Build the shard_mapped attention.  Call inside the mesh context.

    Inputs (global shapes): q [B,H,d] replicated; k_lr [B,N,r], k/v
    [B,N,Hk,d] sharded on dim 1 over ``axis``; k_new/v_new [B,Hk,d]
    replicated; length scalar.  Output: [B,H,d] replicated.
    """
    n_shards = mesh.shape[axis]
    quota = max(1, n_select // n_shards)

    def body(q, q_lr, k_lr, k, v, k_new, v_new, length):
        idx = jax.lax.axis_index(axis)
        n_loc = k.shape[1]
        start = idx * n_loc
        m_i, l_i, o_i = _local_partial(
            q, q_lr, k_lr, k, v, k_new, v_new, start, length,
            group_size=group_size, quota=quota, n_kv_heads=n_kv_heads,
            axis=axis, n_shards=n_shards)
        # flash-decoding combine: only [B,H](+[B,H,d]) partials cross ICI
        m = jax.lax.pmax(m_i, axis)
        w = l_i * jnp.exp(m_i - m)
        denom = jax.lax.psum(w, axis)
        o = jax.lax.psum(o_i * w[..., None], axis) / jnp.maximum(denom, 1e-30)[..., None]
        return o.astype(q.dtype)

    return _shard_map(
        body, mesh,
        in_specs=(P(), P(), P(None, axis, None), P(None, axis, None, None),
                  P(None, axis, None, None), P(), P(), P()),
        out_specs=P(),
    )


def reference_decode_attn(q, q_lr, k_lr, k, v, k_new, v_new, length,
                          *, group_size, n_select, n_shards=1):
    """Single-host oracle with the same per-shard-quota semantics."""
    b, h, d = q.shape
    n = k.shape[1]
    n_loc = n // n_shards
    quota = max(1, n_select // n_shards)
    sel_k, sel_v, sel_mask = [], [], []
    for sh in range(n_shards):
        sl = slice(sh * n_loc, (sh + 1) * n_loc)
        scores = jnp.einsum("bhr,bnr->bn", q_lr, k_lr[:, sl])
        pos = sh * n_loc + jnp.arange(n_loc)
        scores = jnp.where((pos < length)[None, :], scores, NEG)
        g = group_size
        gsc = scores.reshape(b, n_loc // g, g).max(axis=-1)
        top_sc, gids = jax.lax.top_k(gsc, min(quota, n_loc // g))
        valid = top_sc > NEG / 2
        tok = (gids[..., None] * g + jnp.arange(g)).reshape(b, -1)
        sel_k.append(jnp.take_along_axis(k[:, sl], tok[..., None, None], axis=1))
        sel_v.append(jnp.take_along_axis(v[:, sl], tok[..., None, None], axis=1))
        sel_mask.append(((sh * n_loc + tok) < length) & jnp.repeat(valid, g, axis=-1))
    from repro.models.layers import decode_attention
    return decode_attention(q, jnp.concatenate(sel_k, 1), jnp.concatenate(sel_v, 1),
                            jnp.concatenate(sel_mask, 1), k_new, v_new)
