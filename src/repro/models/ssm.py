"""State-space & recurrent blocks: Mamba2 (SSD) and xLSTM (mLSTM / sLSTM).

These are the attention-free families among the assigned architectures.  They
carry O(1) decode state, so KVSwap's disk offloading is inapplicable to them
(see DESIGN.md §Arch-applicability) — but they must be first-class citizens of
the serving/training stack.

Mamba2 uses the chunked SSD formulation (quadratic within a chunk, linear
scan across chunks) so prefill at 32K lowers without materializing per-step
states.  mLSTM/sLSTM use ``lax.scan`` over time (sLSTM's hidden recurrence is
inherently sequential).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, init_rmsnorm, rmsnorm

# --------------------------------------------------------------------------
# Mamba2 (scalar-identity A per head; SSD chunked algorithm)
# --------------------------------------------------------------------------


def init_mamba2(key, *, d_model: int, d_state: int, d_conv: int = 4,
                expand: int = 2, head_p: int = 64, dtype=jnp.float32):
    d_inner = expand * d_model
    n_heads = d_inner // head_p
    conv_dim = d_inner + 2 * d_state
    ks = jax.random.split(key, 5)
    return {
        # projects to [z (gate), x, B, C, dt]
        "in_proj": dense_init(ks[0], (d_model, 2 * d_inner + 2 * d_state + n_heads), dtype),
        "conv_w": jax.random.normal(ks[1], (conv_dim, d_conv), dtype) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, float(n_heads), n_heads).astype(dtype)),
        "d_skip": jnp.ones((n_heads,), dtype),
        "dt_bias": jnp.zeros((n_heads,), dtype),
        "norm": init_rmsnorm(d_inner, dtype),
        "out_proj": dense_init(ks[2], (d_inner, d_model), dtype),
    }


def mamba2_meta(p) -> dict:
    """Derive dims from param shapes (keeps params a pure array pytree)."""
    d_inner = p["out_proj"].shape[0]
    d_conv = p["conv_w"].shape[1]
    d_state = (p["conv_w"].shape[0] - d_inner) // 2
    n_heads = p["a_log"].shape[0]
    return {"d_inner": d_inner, "n_heads": n_heads, "head_p": d_inner // n_heads,
            "d_state": d_state, "d_conv": d_conv}


def mamba2_init_state(p, batch: int, dtype=jnp.float32):
    m = mamba2_meta(p)
    return {
        "conv": jnp.zeros((batch, m["d_inner"] + 2 * m["d_state"], m["d_conv"] - 1), dtype),
        "ssm": jnp.zeros((batch, m["n_heads"], m["head_p"], m["d_state"]), dtype),
    }


def _mamba2_split(p, x):
    """in_proj + split.  x [B,S,D] → z, xc, b, c, dt."""
    m = mamba2_meta(p)
    di, ds, nh = m["d_inner"], m["d_state"], m["n_heads"]
    zxbcdt = x @ p["in_proj"]
    z, xc, b, c, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + ds, 2 * di + 2 * ds], axis=-1)
    return z, xc, b, c, dt


def _causal_conv(p, xbc, conv_state=None):
    """Depthwise causal conv1d.  ``xbc [B, S, C]`` (+ optional carried state
    of the last ``d_conv-1`` inputs) → same shape + new state."""
    m = mamba2_meta(p)
    dk = m["d_conv"]
    b, s, cdim = xbc.shape
    seq = xbc.transpose(0, 2, 1)  # [B,C,S]
    if conv_state is None:
        pad = jnp.zeros((b, cdim, dk - 1), xbc.dtype)
    else:
        pad = conv_state
    full = jnp.concatenate([pad, seq], axis=-1)          # [B,C,S+dk-1]
    idx = jnp.arange(s)[:, None] + jnp.arange(dk)[None, :]
    windows = full[:, :, idx]                             # [B,C,S,dk]
    out = jnp.einsum("bcsk,ck->bcs", windows, p["conv_w"]) + p["conv_b"][None, :, None]
    out = jax.nn.silu(out).transpose(0, 2, 1)             # [B,S,C]
    new_state = full[:, :, -(dk - 1):]
    return out, new_state


def mamba2_forward(p, x: jax.Array, state=None, *, chunk: int = 128,
                   use_pallas: bool = False):
    """Full-sequence forward.  ``x [B, S, D]`` → ``(y [B, S, D], state)``.

    Chunked SSD: intra-chunk is a decayed quadratic form; inter-chunk carries
    ``h [B, H, P, N]`` through a ``lax.scan`` over chunks.  ``use_pallas``
    routes the intra-chunk quadratic through the SSD Pallas kernel
    (repro.kernels.ssd_chunk; interpret mode on CPU).
    """
    m = mamba2_meta(p)
    nh, hp, ds = m["n_heads"], m["head_p"], m["d_state"]
    bsz, s, _ = x.shape
    if state is None:
        state = mamba2_init_state(p, bsz, x.dtype)

    z, xc, bmat, cmat, dt = _mamba2_split(p, x)
    xbc = jnp.concatenate([xc, bmat, cmat], axis=-1)
    xbc, conv_state = _causal_conv(p, xbc, state["conv"])
    xc, bmat, cmat = jnp.split(xbc, [m["d_inner"], m["d_inner"] + ds], axis=-1)

    # SSD recurrence in float32: exp/cumsum chains underflow in bf16, and a
    # mixed-precision carry would break the scan's type invariant.
    in_dtype = x.dtype
    state_dtype = state["ssm"].dtype
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))          # [H] (negative)
    log_decay = dt * a[None, None, :]                     # [B,S,H]  (= log a_t)
    xh = xc.astype(jnp.float32).reshape(bsz, s, nh, hp)   # [B,S,H,P]
    bmat = bmat.astype(jnp.float32)
    cmat = cmat.astype(jnp.float32)

    q = chunk
    pad = (-s) % q
    if pad:
        zpad = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        xh, bmat, cmat, dt, log_decay = map(zpad, (xh, bmat, cmat, dt, log_decay))
    nc = (s + pad) // q
    xh = xh.reshape(bsz, nc, q, nh, hp)
    bm = bmat.reshape(bsz, nc, q, ds)
    cm = cmat.reshape(bsz, nc, q, ds)
    dtc = dt.reshape(bsz, nc, q, nh)
    ld = log_decay.reshape(bsz, nc, q, nh)

    cum = jnp.cumsum(ld, axis=2)                          # [B,nc,q,H]
    if use_pallas:
        from repro.kernels.ssd_chunk import ssd_chunk_pallas
        y_intra = ssd_chunk_pallas(xh, bm, cm, dtc, cum)
    else:
        # intra-chunk decayed scores: L[i,j] = exp(cum_i - cum_j), i >= j
        li = cum[:, :, :, None, :]                        # i
        lj = cum[:, :, None, :, :]                        # j
        causal = jnp.tril(jnp.ones((q, q), bool))
        decay_ij = jnp.exp(jnp.where(causal[None, None, :, :, None], li - lj, -jnp.inf))
        cb = jnp.einsum("bnis,bnjs->bnij", cm, bm)        # [B,nc,q,q]
        w = cb[..., None] * decay_ij * dtc[:, :, None, :, :]  # [B,nc,i,j,H]
        y_intra = jnp.einsum("bnijh,bnjhp->bnihp", w, xh)

    # inter-chunk state scan
    chunk_decay = jnp.exp(cum[:, :, -1])                  # [B,nc,H] total decay
    # contribution of each in-chunk token to end-of-chunk state
    tail = jnp.exp(cum[:, :, -1:, :] - cum)               # exp(Σ_{k>j} l_k) [B,nc,q,H]
    db = (dtc * tail)[..., None] * bm[:, :, :, None, :]   # [B,nc,q,H,N]
    chunk_state = jnp.einsum("bkqhn,bkqhp->bkhpn", db, xh)  # [B,nc,H,P,N]

    def scan_fn(h, inp):
        cdec, cstate = inp                                # [B,H], [B,H,P,N]
        h_start = h
        h = cdec[..., None, None] * h + cstate
        return h, h_start

    h_final, h_starts = jax.lax.scan(
        scan_fn, state["ssm"].astype(jnp.float32),
        (chunk_decay.transpose(1, 0, 2), chunk_state.transpose(1, 0, 2, 3, 4)),
    )
    h_starts = h_starts.transpose(1, 0, 2, 3, 4)          # [B,nc,H,P,N]
    inter_decay = jnp.exp(cum)                            # decay from chunk start
    y_inter = jnp.einsum("bnqs,bnhps->bnqhp", cm, h_starts) * inter_decay[..., None]

    y = (y_intra + y_inter).reshape(bsz, nc * q, nh, hp)[:, :s]
    y = y + xc.astype(jnp.float32).reshape(bsz, s, nh, hp) \
        * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(bsz, s, nh * hp).astype(in_dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    return y @ p["out_proj"], {"conv": conv_state,
                               "ssm": h_final.astype(state_dtype)}


def mamba2_step(p, x: jax.Array, state):
    """Single-token decode.  ``x [B, D]`` → ``(y [B, D], state)``."""
    m = mamba2_meta(p)
    nh, hp, ds, dk = m["n_heads"], m["head_p"], m["d_state"], m["d_conv"]
    bsz = x.shape[0]
    z, xc, bmat, cmat, dt = _mamba2_split(p, x[:, None])  # seq dim = 1
    xbc = jnp.concatenate([xc, bmat, cmat], axis=-1)[:, 0]      # [B,C]
    conv = jnp.concatenate([state["conv"], xbc[:, :, None]], axis=-1)  # [B,C,dk]
    out = jnp.einsum("bck,ck->bc", conv, p["conv_w"]) + p["conv_b"]
    out = jax.nn.silu(out)
    new_conv = conv[:, :, 1:]
    xc1, b1, c1 = jnp.split(out, [m["d_inner"], m["d_inner"] + ds], axis=-1)

    dt1 = jax.nn.softplus(dt[:, 0] + p["dt_bias"])              # [B,H]
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt1 * a[None, :])                           # [B,H]
    xh = xc1.reshape(bsz, nh, hp)
    h = decay[..., None, None] * state["ssm"] + \
        (dt1[..., None, None] * xh[..., None]) * b1[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, c1) + xh * p["d_skip"][None, :, None]
    y = y.reshape(bsz, nh * hp)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z[:, 0]))
    return y @ p["out_proj"], {"conv": new_conv, "ssm": h}


# --------------------------------------------------------------------------
# xLSTM: mLSTM (matrix memory) and sLSTM (scalar memory, hidden recurrence)
# --------------------------------------------------------------------------


def init_mlstm(key, *, d_model: int, n_heads: int, dtype=jnp.float32):
    hd = d_model // n_heads
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d_model, d_model), dtype),
        "wk": dense_init(ks[1], (d_model, d_model), dtype),
        "wv": dense_init(ks[2], (d_model, d_model), dtype),
        "w_if": dense_init(ks[3], (d_model, 2 * n_heads), dtype, scale=0.02),
        "b_if": jnp.concatenate([jnp.zeros((n_heads,), dtype),
                                 jnp.full((n_heads,), 3.0, dtype)]),
        "wo": dense_init(ks[4], (d_model, d_model), dtype),
        "norm": init_rmsnorm(hd, dtype),
    }


def mlstm_meta(p) -> dict:
    n_heads = p["b_if"].shape[0] // 2
    return {"n_heads": n_heads, "head_dim": p["wq"].shape[0] // n_heads}


def mlstm_init_state(p, batch: int, dtype=jnp.float32):
    m = mlstm_meta(p)
    h, d = m["n_heads"], m["head_dim"]
    return {
        "c": jnp.zeros((batch, h, d, d), dtype),   # matrix memory
        "n": jnp.zeros((batch, h, d), dtype),      # normalizer
        "m": jnp.full((batch, h), -jnp.inf, dtype),  # stabilizer
    }


def _mlstm_gates(p, x):
    m = mlstm_meta(p)
    h = m["n_heads"]
    g = x @ p["w_if"] + p["b_if"]
    return g[..., :h], g[..., h:]  # pre-activation i, f


def _mlstm_cell(p, state, qkv_if):
    """One step.  q,k,v: [B,H,d]; i_pre,f_pre: [B,H]."""
    q, k, v, i_pre, f_pre = qkv_if
    d = q.shape[-1]
    m_prev = state["m"]
    logf = -jax.nn.softplus(-f_pre)                     # log σ(f)
    m_new = jnp.maximum(logf + m_prev, i_pre)
    i = jnp.exp(i_pre - m_new)
    f = jnp.exp(logf + m_prev - m_new)
    k_s = k / jnp.sqrt(jnp.array(d, k.dtype))
    c = f[..., None, None] * state["c"] + i[..., None, None] * (k_s[..., :, None] * v[..., None, :])
    n = f[..., None] * state["n"] + i[..., None] * k_s
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q)), jnp.exp(-m_new))
    y = jnp.einsum("bhd,bhde->bhe", q, c) / denom[..., None]
    return {"c": c, "n": n, "m": m_new}, y


def mlstm_forward(p, x: jax.Array, state=None):
    """``x [B,S,D]`` → ``(y [B,S,D], state)`` via scan over time."""
    m = mlstm_meta(p)
    h, hd = m["n_heads"], m["head_dim"]
    bsz, s, dmod = x.shape
    if state is None:
        state = mlstm_init_state(p, bsz, jnp.float32)
    q = (x @ p["wq"]).reshape(bsz, s, h, hd)
    k = (x @ p["wk"]).reshape(bsz, s, h, hd)
    v = (x @ p["wv"]).reshape(bsz, s, h, hd)
    ip, fp = _mlstm_gates(p, x)                          # [B,S,H]

    def step(st, inp):
        st, y = _mlstm_cell(p, st, inp)
        return st, y

    seq = (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3),
           ip.transpose(1, 0, 2), fp.transpose(1, 0, 2))
    state, ys = jax.lax.scan(step, state, seq)
    ys = ys.transpose(1, 0, 2, 3)                        # [B,S,H,d]
    ys = rmsnorm(p["norm"], ys).reshape(bsz, s, dmod)
    return ys @ p["wo"], state


def mlstm_step(p, x: jax.Array, state):
    m = mlstm_meta(p)
    h, hd = m["n_heads"], m["head_dim"]
    bsz, dmod = x.shape
    q = (x @ p["wq"]).reshape(bsz, h, hd)
    k = (x @ p["wk"]).reshape(bsz, h, hd)
    v = (x @ p["wv"]).reshape(bsz, h, hd)
    ip, fp = _mlstm_gates(p, x)
    state, y = _mlstm_cell(p, state, (q, k, v, ip, fp))
    y = rmsnorm(p["norm"], y).reshape(bsz, dmod)
    return y @ p["wo"], state


def init_slstm(key, *, d_model: int, n_heads: int, dtype=jnp.float32):
    hd = d_model // n_heads
    ks = jax.random.split(key, 3)
    return {
        # input → [z, i, f, o] and hidden → same (true recurrence)
        "w_x": dense_init(ks[0], (d_model, 4 * d_model), dtype),
        "w_h": dense_init(ks[1], (d_model, 4 * d_model), dtype, scale=0.02),
        "b": jnp.zeros((4 * d_model,), dtype),
        "norm": init_rmsnorm(hd, dtype),
        "wo": dense_init(ks[2], (d_model, d_model), dtype),
    }


def slstm_meta(p) -> dict:
    hd = p["norm"]["scale"].shape[0]
    return {"n_heads": p["w_x"].shape[0] // hd, "head_dim": hd}


def slstm_init_state(p, batch: int, dtype=jnp.float32):
    m = slstm_meta(p)
    h, hd = m["n_heads"], m["head_dim"]
    shape = (batch, h, hd)
    return {
        "c": jnp.zeros(shape, dtype), "n": jnp.zeros(shape, dtype),
        "h": jnp.zeros(shape, dtype), "m": jnp.full((batch, h), -jnp.inf, dtype),
    }


def _slstm_cell(p, state, x_t):
    m = slstm_meta(p)
    hds = m["head_dim"]
    nh = m["n_heads"]
    bsz, dmod = x_t.shape
    h_flat = state["h"].reshape(bsz, dmod)
    g = x_t @ p["w_x"] + h_flat @ p["w_h"] + p["b"]
    z, i_pre, f_pre, o = jnp.split(g, 4, axis=-1)
    rs = lambda t: t.reshape(bsz, nh, hds)
    z, o = jnp.tanh(rs(z)), jax.nn.sigmoid(rs(o))
    # exponential gating with per-head stabilizer (use head-mean pre-acts)
    i_h = rs(i_pre).mean(-1)
    f_h = rs(f_pre).mean(-1)
    logf = -jax.nn.softplus(-f_h)
    m_new = jnp.maximum(logf + state["m"], i_h)
    i = jnp.exp(i_h - m_new)[..., None]
    f = jnp.exp(logf + state["m"] - m_new)[..., None]
    c = f * state["c"] + i * z
    n = f * state["n"] + i
    h_new = o * (c / jnp.maximum(n, 1.0))
    return {"c": c, "n": n, "h": h_new, "m": m_new}, h_new


def slstm_forward(p, x: jax.Array, state=None):
    bsz, s, dmod = x.shape
    if state is None:
        state = slstm_init_state(p, bsz, jnp.float32)

    def step(st, x_t):
        st, h = _slstm_cell(p, st, x_t)
        return st, h

    state, hs = jax.lax.scan(step, state, x.transpose(1, 0, 2))
    hs = hs.transpose(1, 0, 2, 3)                        # [B,S,H,d]
    hs = rmsnorm(p["norm"], hs).reshape(bsz, s, dmod)
    return hs @ p["wo"], state


def slstm_step(p, x: jax.Array, state):
    state, h = _slstm_cell(p, state, x)
    bsz = x.shape[0]
    h = rmsnorm(p["norm"], h).reshape(bsz, -1)
    return h @ p["wo"], state
