"""Shared building blocks: norms, RoPE, GQA attention, SwiGLU, MoE.

Pure-functional JAX: params are plain dicts of arrays; every layer ships an
``init_*`` and an ``apply``-style function.  Sharding is applied externally by
partition rules over param path names (repro.sharding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 500000.0) -> jax.Array:
    """Rotary embedding.  ``x: [..., seq, heads, d]`` (or ``[..., heads, d]``
    with matching positions), ``positions: [..., seq]``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                        # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]                 # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype=jnp.float32, scale: float | None = None):
    fan_in = shape[0]
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return jax.random.normal(key, shape, dtype) * s


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------

def init_attention(key, *, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                   qk_norm: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": dense_init(ks[1], (d_model, n_kv_heads * head_dim), dtype),
        "wv": dense_init(ks[2], (d_model, n_kv_heads * head_dim), dtype),
        "wo": dense_init(ks[3], (n_heads * head_dim, d_model), dtype),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(head_dim, dtype)
        p["k_norm"] = init_rmsnorm(head_dim, dtype)
    return p


def attention_qkv(p, x: jax.Array, positions: jax.Array, *, n_heads: int,
                  n_kv_heads: int, head_dim: int, rope_theta: float, qk_norm: bool,
                  rope: bool = True):
    """Project + (qk-)norm + RoPE (none where ``rope`` is off: a NoPE layer).
    ``x: [B, S, D]`` → q [B,S,H,d], k/v [B,S,Hk,d]."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(b, s, n_kv_heads, head_dim)
    if qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[..., Hk, d] -> [..., Hk*n_rep, d]"""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def _window_mask(mask: jax.Array, q_pos: jax.Array, k_pos: jax.Array,
                 window: int) -> jax.Array:
    """``mask`` narrowed to the ``window`` keys up to and including each
    query's own position (0: no window)."""
    if not window:
        return mask
    return mask & (q_pos[:, None] - k_pos[None, :] < window)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     window: int = 0) -> jax.Array:
    """Causal attention, over the last ``window`` keys where ``window`` is
    set.  q [B,S,H,d], k/v [B,S,Hk,d] → [B,S,H,d]."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    kq = repeat_kv(k, h // hk)
    vq = repeat_kv(v, h // hk)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kq) / jnp.sqrt(d).astype(q.dtype)
    mask = jnp.tril(jnp.ones((s, s), bool))
    mask = _window_mask(mask, jnp.arange(s), jnp.arange(s), window)
    scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vq)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             q_start: int, window: int = 0) -> jax.Array:
    """Causal attention for a *suffix chunk* of queries over the full keys.

    ``q [B,Sq,H,d]`` covers positions ``[q_start, q_start+Sq)`` and
    ``k/v [B,Sk,Hk,d]`` positions ``[0, Sk)`` (cached prefix KV
    concatenated with the chunk's own KV), both counted from the first key:
    a windowed layer's prefix may hold only its last keys.  ``window``
    narrows each query to the last ``window`` keys.  With ``q_start=0`` and
    ``Sq == Sk`` this is exactly :func:`causal_attention` — the chunked path
    computes the same score rows, so restoring bit-identical prefix KV makes
    warm prefill bit-identical to cold (see ``KVSwapEngine.prefill_cached``).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    kq = repeat_kv(k, h // hk)
    vq = repeat_kv(v, h // hk)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kq) / jnp.sqrt(d).astype(q.dtype)
    # the [Sq, Sk] slice of the causal mask, built directly (an Sk×Sk tril
    # would be quadratic in the cached context for a tiny suffix)
    q_pos, k_pos = q_start + jnp.arange(sq), jnp.arange(sk)
    mask = _window_mask(q_pos[:, None] >= k_pos[None, :], q_pos, k_pos, window)
    scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vq)


def bidirectional_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            mask: jax.Array | None = None) -> jax.Array:
    """Encoder / cross attention.  q [B,Sq,H,d], k/v [B,Sk,Hk,d]."""
    h = q.shape[2]
    hk = k.shape[2]
    kq = repeat_kv(k, h // hk)
    vq = repeat_kv(v, h // hk)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kq) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :], scores, jnp.finfo(scores.dtype).min)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vq)


def decode_attention(q: jax.Array, k_ctx: jax.Array, v_ctx: jax.Array,
                     ctx_mask: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     *, use_pallas: bool = False) -> jax.Array:
    """One-token decode over an assembled (masked) context plus self.

    q [B,H,d]; k_ctx/v_ctx [B,N,Hk,d]; ctx_mask [B,N]; k_new/v_new [B,Hk,d].
    Returns [B,H,d].  ``use_pallas`` routes through the flash-decode kernel
    (repro.kernels.gather_attention).
    """
    b, h, d = q.shape
    hk = k_ctx.shape[2]
    k_all = jnp.concatenate([k_ctx, k_new[:, None]], axis=1)
    v_all = jnp.concatenate([v_ctx, v_new[:, None]], axis=1)
    mask = jnp.concatenate([ctx_mask, jnp.ones((b, 1), bool)], axis=1)
    if use_pallas:
        from repro.kernels import ops
        return ops.gather_attention(q, k_all, v_all, mask).astype(q.dtype)
    kq = repeat_kv(k_all, h // hk)
    vq = repeat_kv(v_all, h // hk)
    scores = jnp.einsum("bhd,bnhd->bhn", q, kq) / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.where(mask[:, None, :], scores, jnp.finfo(scores.dtype).min)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhn,bnhd->bhd", w, vq)


@jax.jit
def gather_slots(dev_k: jax.Array, dev_v: jax.Array, slots: jax.Array,
                 tail_k: jax.Array, tail_v: jax.Array,
                 tail_fill: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Assemble the decode context from persistent device buffers.

    The device-resident analogue of ``KVCacheManager.gather``: instead of a
    fresh host concat + full upload per layer, the selected-KV working set
    already lives on device (``dev_k/dev_v [B, C, G, H_kv, d]`` — the reuse
    buffer's mirror) and this gathers it by the step's slot permutation.

    ``slots [B, M]`` is the full per-step addressing: slot index where the
    group is resident, ``-1`` where the selection mask is off (clamped for
    the gather, turned into the token mask here — no separate mask upload),
    ``-2`` for transiently staged groups (gathered wrong on purpose; the
    caller overrides those rows).  ``tail_k/tail_v [B, G, H_kv, d]`` is the
    device rolling mirror — the most recent ``< G`` decoded tokens per row,
    written in place by the engine, never round-tripped — and ``tail_fill
    [B]`` its per-row valid count: under continuous batching rows sit at
    different fill levels, so validity is a data-dependent mask rather than
    a shape, and the context compiles once for all fill levels.

    Returns ``(k_ctx, v_ctx, token_mask)`` with ``k_ctx [B, M·G + G,
    H_kv, d]`` — the exact shape/dtype/values the host-gather path feeds
    ``decode_block``, except that slots the mask disables hold stale (finite)
    data rather than zeros; masked attention weights underflow to exactly 0
    either way, which is what keeps the two paths bit-identical.
    """
    b, m = slots.shape
    c, g = dev_k.shape[1], dev_k.shape[2]
    idx = jnp.clip(slots, 0, c - 1)[..., None, None, None]        # [B,M,1,1,1]
    k_sel = jnp.take_along_axis(dev_k, idx, axis=1)               # [B,M,G,Hk,d]
    v_sel = jnp.take_along_axis(dev_v, idx, axis=1)
    k_ctx = k_sel.reshape(b, m * g, *dev_k.shape[3:])
    v_ctx = v_sel.reshape(b, m * g, *dev_v.shape[3:])
    tok_mask = jnp.repeat(slots != -1, g, axis=1)                 # [B, M·G]
    k_ctx = jnp.concatenate([k_ctx, tail_k.astype(dev_k.dtype)], axis=1)
    v_ctx = jnp.concatenate([v_ctx, tail_v.astype(dev_v.dtype)], axis=1)
    tail_mask = jnp.arange(g)[None, :] < tail_fill[:, None]       # [B, G]
    tok_mask = jnp.concatenate([tok_mask, tail_mask], axis=1)
    return k_ctx, v_ctx, tok_mask


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
    }


def swiglu(p, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    return {
        "w_up": dense_init(ks[0], (d_model, d_ff), dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(p, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ p["w_up"] + p["b_up"]) @ p["w_down"] + p["b_down"]


# --------------------------------------------------------------------------
# Mixture of Experts (GShard-style dispatch/combine; expert axis shardable)
# --------------------------------------------------------------------------

def init_moe(key, *, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32,
             shared_d_ff: int = 0):
    ks = jax.random.split(key, 5)
    s = 1.0 / np.sqrt(d_model)
    p = {
        "router": dense_init(ks[0], (d_model, n_experts), dtype, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (n_experts, d_model, d_ff), dtype) * s,
        "w_up": jax.random.normal(ks[2], (n_experts, d_model, d_ff), dtype) * s,
        "w_down": jax.random.normal(ks[3], (n_experts, d_ff, d_model), dtype) / np.sqrt(d_ff),
    }
    if shared_d_ff:
        p["shared"] = init_swiglu(ks[4], d_model, shared_d_ff, dtype)
    return p


def moe(p, x: jax.Array, *, top_k: int, capacity_factor: float | None = 1.25,
        act=jax.nn.silu, logits: jax.Array | None = None):
    """Top-k routed MoE.  ``x: [B, S, D]`` (or one decode token per row,
    ``[B, D]``) → ``(y, aux_loss scalar)``, ``y`` shaped as ``x``.

    The router's ``logits`` default to ``x @ router``; a model whose router
    reads another input (before attention) passes them in.  The top-k
    gates are renormalised, and each expert is ``W_down(act(x W_gate) ⊙
    x W_up)``.

    With a ``capacity_factor``, tokens are scattered into a per-expert
    buffer ``[B, E, C, D]`` (positions past capacity are dropped), the
    experts run batched over the ``E`` axis (which is what gets sharded
    expert-parallel), and outputs gather back.  Memory is
    O(B·(E·C + S·K)·D) — no dense ``[B,S,E,C]`` one-hots — and compute
    scales with ``top_k``, not ``n_experts``.  ``capacity_factor=None`` is
    dropless: every assignment is computed (:func:`_moe_dropless`).
    """
    if logits is None:
        logits = x @ p["router"]
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # [..., K]
    gate_vals = gate_vals / (gate_vals.sum(axis=-1, keepdims=True) + 1e-9)
    if capacity_factor is None:
        y = _moe_dropless(p, x, gate_vals, gate_idx, act)
    else:
        y = _moe_capacity(p, x, gate_vals, gate_idx, act, capacity_factor)
    if "shared" in p:
        y = y + swiglu(p["shared"], x)

    # Switch-style load-balance loss
    me = probs.reshape(-1, e).mean(axis=0)                             # [E]
    ce = jax.nn.one_hot(gate_idx, e).sum(axis=-2).reshape(-1, e).mean(axis=0)  # routed frac
    aux = e * jnp.sum(me * ce)
    return y, aux


def _moe_capacity(p, x, gate_vals, gate_idx, act, capacity_factor):
    """Capacity-bounded scatter dispatch (see :func:`moe`); a ``[B, D]``
    decode batch routes as ``[B, 1, D]``."""
    shape = x.shape
    if x.ndim == 2:
        x, gate_vals, gate_idx = x[:, None], gate_vals[:, None], gate_idx[:, None]
    b, s, d = x.shape
    e = p["w_gate"].shape[0]
    top_k = gate_idx.shape[-1]
    cap = max(1, int(np.ceil(s * top_k / e * capacity_factor)))
    t = s * top_k                                          # assignments per row
    expert_of = gate_idx.reshape(b, t)                     # [B,T]
    token_of = jnp.repeat(jnp.arange(s), top_k)[None, :].repeat(b, 0)  # [B,T]
    gates = gate_vals.reshape(b, t)

    # position of each assignment within its expert's queue
    assign_1h = jax.nn.one_hot(expert_of, e, dtype=jnp.int32)          # [B,T,E]
    pos_all = jnp.cumsum(assign_1h, axis=1) - assign_1h                # [B,T,E]
    pos = jnp.take_along_axis(pos_all, expert_of[..., None], axis=-1)[..., 0]  # [B,T]
    keep = pos < cap
    pos_safe = jnp.where(keep, pos, cap)                   # cap = out-of-bounds → drop

    x_tok = jnp.take_along_axis(x, token_of[..., None], axis=1)        # [B,T,D]
    bidx = jnp.arange(b)[:, None].repeat(t, 1)
    buf = jnp.zeros((b, e, cap, d), x.dtype)
    buf = buf.at[bidx, expert_of, pos_safe].set(x_tok, mode="drop")

    h = act(jnp.einsum("becd,edf->becf", buf, p["w_gate"]))
    h = h * jnp.einsum("becd,edf->becf", buf, p["w_up"])
    out = jnp.einsum("becf,efd->becd", h, p["w_down"])     # [B,E,C,D]

    y_tok = out[bidx, expert_of, pos_safe.clip(0, cap - 1)]            # [B,T,D]
    y_tok = y_tok * (gates * keep)[..., None]
    y = jnp.zeros_like(x).at[bidx, token_of].add(y_tok)
    return y.reshape(shape)


def _moe_dropless(p, x, gate_vals, gate_idx, act):
    """Every top-k assignment computed, whatever the routing: the
    assignments sorted by expert, and each expert matrix applied to its own
    run of rows by one grouped matmul (``jax.lax.ragged_dot``, a grouped
    kernel on the TPU), so only the experts routed to are read.  The gated
    sum is taken in float32."""
    d = x.shape[-1]
    e = p["w_gate"].shape[0]
    top_k = gate_idx.shape[-1]
    xt = x.reshape(-1, d)
    expert_of = gate_idx.reshape(-1)                       # [N·K]
    order = jnp.argsort(expert_of)
    token_of = order // top_k
    sizes = jnp.bincount(expert_of, length=e).astype(jnp.int32)
    xs = xt[token_of]
    h = act(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) \
        * jax.lax.ragged_dot(xs, p["w_up"], sizes)
    out = jax.lax.ragged_dot(h, p["w_down"], sizes)       # [N·K, D]
    gates = gate_vals.reshape(-1)[order]
    y = jnp.zeros(xt.shape, jnp.float32).at[token_of].add(
        out.astype(jnp.float32) * gates[:, None])
    return y.astype(x.dtype).reshape(x.shape)
