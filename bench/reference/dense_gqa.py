"""Plain reference for a dense decoder with grouped-query attention.

Written from the published description (Llama-style block; Qwen3 adds a
per-head RMSNorm on queries and keys) and nothing of the program:

    h = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv   (per head; q, k RMSNorm'd
    when the config has qk_norm), rotary embedding on q and k (rotate-half,
    inv_freq = theta^(-2i/d));  o = softmax(q k^T / sqrt(d), causal) v with
    query head h reading KV head h // (H / H_kv);  x += o Wo;
    x += W_down(silu(h2 W_gate) * (h2 W_up)) with h2 = RMSNorm(x);
    logits = RMSNorm(x) W_head  (W_head = embedding^T when tied).

Full attention over every earlier token: no selection, no cache, no
batching.  It runs layer by layer over query chunks of ``CHUNK`` tokens
against the keys so far, so a 10k-token sequence fits beside the weights.
Everything is float32 with every matmul at ``highest`` precision.

``fp8=True`` is the control, the next precision below the bfloat16 that
the configurations serve: every weight, every matmul operand and the
stored K and V are rounded to float8 e4m3 under an amax scale (one per
weight matrix, one per activation row), as an fp8 serving path would
hold them; the arithmetic between stays float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1024
FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


def _round_fp8(x, axis=None):
    """``x`` rounded to float8 e4m3 under an amax scale over ``axis``
    (``None``: the whole tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(x.dtype) * scale


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, pos, theta):
    # x [S, heads, d], pos [S]
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"), donate_argnums=(1, 2, 3))
def _layer_chunk(p, x_all, k_buf, v_buf, start, *, dims, fp8=False):
    """One layer over query positions [start, start + CHUNK) of the stream
    ``x_all``, written back in place: later chunks read only their own rows.
    K/V of the chunk go into the buffers at ``start``; keys after a query
    are masked."""
    nh, nkv, hd, eps, theta, qk_norm = dims
    r = (lambda a: _round_fp8(a, -1)) if fp8 else (lambda a: a)
    c = CHUNK
    x = jax.lax.dynamic_slice_in_dim(x_all, start, c)
    pos = start + jnp.arange(c)
    h = r(_rmsnorm(x, p["attn_norm"]["scale"], eps))
    at = p["attn"]
    q = (h @ at["wq"]).reshape(c, nh, hd)
    k = (h @ at["wk"]).reshape(c, nkv, hd)
    v = (h @ at["wv"]).reshape(c, nkv, hd)
    if qk_norm:
        q = _rmsnorm(q, at["q_norm"]["scale"], eps)
        k = _rmsnorm(k, at["k_norm"]["scale"], eps)
    q = r(_rope(q, pos, theta))
    k = r(_rope(k, pos, theta))
    v = r(v)
    k_buf = jax.lax.dynamic_update_slice(k_buf, k, (start, 0, 0))
    v_buf = jax.lax.dynamic_update_slice(v_buf, v, (start, 0, 0))
    rep = nh // nkv
    kq = jnp.repeat(k_buf, rep, axis=1)
    vq = jnp.repeat(v_buf, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kq) / jnp.sqrt(jnp.asarray(hd, x.dtype))
    mask = pos[:, None] >= jnp.arange(k_buf.shape[0])[None, :]
    scores = jnp.where(mask[None], scores, jnp.finfo(scores.dtype).min)
    w = r(jax.nn.softmax(scores, axis=-1))
    o = r(jnp.einsum("hqk,khd->qhd", w, vq).reshape(c, nh * hd))
    x = x + o @ at["wo"]
    h2 = r(_rmsnorm(x, p["mlp_norm"]["scale"], eps))
    m = p["mlp"]
    x = x + r(jax.nn.silu(h2 @ m["w_gate"]) * (h2 @ m["w_up"])) @ m["w_down"]
    return jax.lax.dynamic_update_slice_in_dim(x_all, x, start, 0), k_buf, v_buf


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _final(x, scale, eps, fp8=False):
    h = _rmsnorm(x, scale, eps)
    return _round_fp8(h, -1) if fp8 else h


@functools.partial(jax.jit, static_argnames=("fp8",))
def _weights(tree, fp8=False):
    """A layer's (or the head's) weights in float32; under ``fp8`` each
    matrix is rounded with its own scale (norm scales stay float32)."""
    def one(a):
        a = a.astype(jnp.float32)
        return _round_fp8(a) if fp8 and a.ndim == 2 else a
    return jax.tree_util.tree_map(one, tree)


def hidden(params, cfg: dict, tokens: np.ndarray, fp8: bool = False) -> jax.Array:
    """Final-normed hidden states ``[S_pad, d]`` of the full forward over
    ``tokens`` (rows past ``len(tokens)`` are padding)."""
    s = int(len(tokens))
    s_pad = -(-s // CHUNK) * CHUNK
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]), bool(cfg["qk_norm"]))
    kv_shape = (s_pad, cfg["num_key_value_heads"], cfg["head_dim"])
    toks = np.zeros(s_pad, np.int32)
    toks[:s] = tokens
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        if fp8:
            x = _round_fp8(x, -1)
        for blk in params["blocks"]:
            p = _weights(blk, fp8)
            k_buf = jnp.zeros(kv_shape, jnp.float32)
            v_buf = jnp.zeros(kv_shape, jnp.float32)
            for c0 in range(0, s_pad, CHUNK):
                x, k_buf, v_buf = _layer_chunk(p, x, k_buf, v_buf, jnp.int32(c0),
                                               dims=dims, fp8=fp8)
            del p, k_buf, v_buf
        return _final(x, params["final_norm"]["scale"].astype(jnp.float32), dims[3], fp8)


@functools.partial(jax.jit, static_argnames=("tied",))
def _stats(x, head, pos, targets, tied):
    h = x[pos]
    logits = (jnp.einsum("nd,vd->nv", h, head) if tied else h @ head).astype(jnp.float32)
    z = jnp.take_along_axis(logits, targets, axis=1)
    return logits.max(axis=1), z, jnp.argmax(logits, axis=1)


def logit_stats(params, cfg: dict, x: jax.Array, out_pos: np.ndarray,
                targets: np.ndarray, fp8: bool = False, rows: int = 256):
    """At positions ``out_pos`` of the hidden states ``x``: the best logit,
    the logits of ``targets [n, k]``, and the argmax token, in row blocks."""
    tied = bool(cfg["tie_word_embeddings"])
    head = _weights(params["embed"] if tied else params["lm_head"], fp8)
    best, zt, top = [], [], []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, len(out_pos), rows):
            n = min(rows, len(out_pos) - r0)
            pb = np.zeros(rows, np.int32)     # one compiled shape per block
            pb[:n] = out_pos[r0:r0 + n]
            tb = np.zeros((rows, targets.shape[1]), np.int32)
            tb[:n] = targets[r0:r0 + n]
            m, z, a = jax.device_get(_stats(x, head, jnp.asarray(pb), jnp.asarray(tb), tied))
            best.append(m[:n])
            zt.append(z[:n])
            top.append(a[:n])
    return np.concatenate(best), np.concatenate(zt), np.concatenate(top)
