"""Plain reference for SmallThinker's decoder: windowed and NoPE full
attention layers, each with a 64-expert ReGLU layer routed before
attention.

Written from the published config and the model card, and nothing of the
program:

    h = RMSNorm(x);  r = h W_router            (the router reads the layer's input)
    q, k, v = h Wq, h Wk, h Wv                 (no bias, no qk-norm)
    windowed layer: rotary embedding on q and k (rotate-half, theta); the
      query at p sees keys p - W + 1 ... p
    full layer: no rotation (NoPE); the query at p sees keys 0 ... p
    x += softmax(q k^T / sqrt(d), mask) v Wo   (query head h reads KV head h // (H / H_kv))
    h2 = RMSNorm(x);  g = softmax(r) kept at its top k entries, renormalised
    x += sum over the top k of g_e W_down,e(relu(h2 W_gate,e) * (h2 W_up,e))
    logits = RMSNorm(x) W_head

A layer's kind comes from the published ``sliding_window_layout`` and
``rope_layout`` at the published index the configuration keeps
(``published_layers``).  The expert layer is computed densely: every
expert on every token, weighted by gates that are zero off the top k.

It runs layer by layer over query chunks of ``CHUNK`` tokens against the
keys so far, with one layer's weights in float32 at a time, so a 16k-token
sequence fits beside the bfloat16 weights.  Everything is float32 with
every matmul at ``highest`` precision.  ``fp8=True`` is the control, as in
``dense_gqa.py``: every weight matrix (each expert's its own), every
matmul operand and the stored K and V rounded to float8 e4m3 under an amax
scale.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _dense_gqa():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense_gqa.py")
    spec = importlib.util.spec_from_file_location("bench_reference_dense_gqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_D = _dense_gqa()
CHUNK = _D.CHUNK
_round_fp8, _rmsnorm, _rope, _final = _D._round_fp8, _D._rmsnorm, _D._rope, _D._final
logit_stats = _D.logit_stats


def layer_kinds(cfg: dict) -> list[tuple[int, bool]]:
    """(window, rope) of each layer the configuration keeps: window 0 is
    attention over every earlier token."""
    out = []
    for p in cfg["published_layers"]:
        window = cfg["sliding_window_size"] if cfg["sliding_window_layout"][p] else 0
        out.append((int(window), bool(cfg["rope_layout"][p])))
    return out


@functools.partial(jax.jit, static_argnames=("dims", "fp8"), donate_argnums=(1, 2, 3))
def _layer_chunk(p, x_all, k_buf, v_buf, start, *, dims, fp8=False):
    """One layer over query positions [start, start + CHUNK) of ``x_all``,
    written back in place; K/V of the chunk go into the buffers at
    ``start``.  Keys after a query, or outside its window, are masked."""
    nh, nkv, hd, eps, theta, window, rope, top_k = dims
    r = (lambda a: _round_fp8(a, -1)) if fp8 else (lambda a: a)
    c = CHUNK
    x = jax.lax.dynamic_slice_in_dim(x_all, start, c)
    pos = start + jnp.arange(c)
    h = r(_rmsnorm(x, p["attn_norm"]["scale"], eps))
    m = p["moe"]
    logits = h @ m["router"]
    at = p["attn"]
    q = (h @ at["wq"]).reshape(c, nh, hd)
    k = (h @ at["wk"]).reshape(c, nkv, hd)
    v = (h @ at["wv"]).reshape(c, nkv, hd)
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q, k, v = r(q), r(k), r(v)
    k_buf = jax.lax.dynamic_update_slice(k_buf, k, (start, 0, 0))
    v_buf = jax.lax.dynamic_update_slice(v_buf, v, (start, 0, 0))
    rep = nh // nkv
    kq = jnp.repeat(k_buf, rep, axis=1)
    vq = jnp.repeat(v_buf, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kq) / jnp.sqrt(jnp.asarray(hd, x.dtype))
    k_pos = jnp.arange(k_buf.shape[0])[None, :]
    mask = pos[:, None] >= k_pos
    if window:
        mask &= pos[:, None] - k_pos < window
    scores = jnp.where(mask[None], scores, jnp.finfo(scores.dtype).min)
    w = r(jax.nn.softmax(scores, axis=-1))
    o = r(jnp.einsum("hqk,khd->qhd", w, vq).reshape(c, nh * hd))
    x = x + o @ at["wo"]
    h2 = r(_rmsnorm(x, p["mlp_norm"]["scale"], eps))
    probs = jax.nn.softmax(logits, axis=-1)
    top = jax.nn.one_hot(jax.lax.top_k(probs, top_k)[1], probs.shape[-1]).sum(axis=1)
    gates = probs * top / jnp.sum(probs * top, axis=-1, keepdims=True)   # [c, E]
    a = jax.nn.relu(jnp.einsum("cd,edf->cef", h2, m["w_gate"]))
    a = r(a * jnp.einsum("cd,edf->cef", h2, m["w_up"]) * gates[..., None])
    x = x + jnp.einsum("cef,efd->cd", a, m["w_down"])
    return jax.lax.dynamic_update_slice_in_dim(x_all, x, start, 0), k_buf, v_buf


@functools.partial(jax.jit, static_argnames=("fp8",))
def _weights(tree, fp8=False):
    """A layer's (or the head's) weights in float32; under ``fp8`` each
    weight matrix, and each expert's, is rounded with its own scale."""
    def one(a):
        a = a.astype(jnp.float32)
        if not fp8 or a.ndim < 2:
            return a
        return _round_fp8(a, (-2, -1)) if a.ndim == 3 else _round_fp8(a)
    return jax.tree_util.tree_map(one, tree)


def hidden(params, cfg: dict, tokens: np.ndarray, fp8: bool = False) -> jax.Array:
    """Final-normed hidden states ``[S_pad, d]`` of the full forward over
    ``tokens`` (rows past ``len(tokens)`` are padding)."""
    s = int(len(tokens))
    s_pad = -(-s // CHUNK) * CHUNK
    eps = float(cfg["rms_norm_eps"])
    kv_shape = (s_pad, cfg["num_key_value_heads"], cfg["head_dim"])
    toks = np.zeros(s_pad, np.int32)
    toks[:s] = tokens
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        if fp8:
            x = _round_fp8(x, -1)
        for blk, (window, rope) in zip(params["blocks"], layer_kinds(cfg)):
            dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
                    eps, float(cfg["rope_theta"]), window, rope,
                    int(cfg["moe_num_active_primary_experts"]))
            p = _weights(blk, fp8)
            k_buf = jnp.zeros(kv_shape, jnp.float32)
            v_buf = jnp.zeros(kv_shape, jnp.float32)
            for c0 in range(0, s_pad, CHUNK):
                x, k_buf, v_buf = _layer_chunk(p, x, k_buf, v_buf, jnp.int32(c0),
                                               dims=dims, fp8=fp8)
            del p, k_buf, v_buf
        return _final(x, params["final_norm"]["scale"].astype(jnp.float32), eps, fp8)
