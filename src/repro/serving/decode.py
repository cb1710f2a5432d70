"""Device-resident serving path (pure JAX, jittable, pjit-shardable).

This is the **TPU-native analogue** of the disk engine in ``repro.core``: the
full KV cache lives in (sharded) device memory, and KVSwap's grouped
low-rank selection decides which KV *groups* the decode attention touches.
On a pod, the cache's sequence axis can be sharded across the ``data`` mesh
axis; selection shrinks the bytes any attention step has to move — the same
insight as the disk version, with ICI/HBM playing the role of the disk.

Two serve modes:

* ``full``   — classic masked decode attention over the whole cache;
* ``kvswap`` — score against the compressed ``k_lr`` (Eq. 1, head-summed),
  ReduceMax over groups of G, top-M groups gathered and attended.

``serve_step`` is functional: takes + returns the cache pytree, so it jits.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.transformer import ATTN_KINDS, ModelConfig

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class KVSwapServeConfig:
    group_size: int = 4
    n_select: int = 100
    rank: int = 64
    # §3.4.1 rolling buffer, device edition: new tokens append into a small
    # (replicated / batch-sharded) buffer so the hot serve_step never does a
    # dynamic-update-slice into the seq-sharded main cache (GSPMD rewrites
    # that into whole-shard select chains — measured 4x the step's HBM
    # traffic).  ``flush_rolling`` merges full groups back, 1/G amortized.
    rolling: bool = False
    # §3.3 cross-layer prediction, device edition: "prev" scores layer i's
    # groups with a query projected from layer i−1's *input*, so the gather
    # for layer i has no data dependence on layer i−1's attention output —
    # XLA's scheduler is free to overlap it, mirroring the disk engine's
    # async prefetch.  "self" (default) scores from the layer's own input.
    predict_from: str = "self"

    @property
    def rb_len(self) -> int:
        return self.group_size


def _is_whisper(cfg) -> bool:
    return type(cfg).__name__ == "WhisperConfig"


def _blocks(cfg) -> tuple:
    return ("attn",) * cfg.n_layers if _is_whisper(cfg) else cfg.blocks


# --------------------------------------------------------------------------
# adapters as params
# --------------------------------------------------------------------------

def attach_kvswap_adapters(key, params, cfg, rank: int, dtype=jnp.float32):
    """Add per-KV-layer low-rank adapters ``A [H_k·d, r]`` to the params tree.

    In production these come from offline SVD (repro.core.lowrank.fit_adapter)
    on calibration data; random orthonormal init keeps the same shapes and
    flops without calibration data.
    """
    feat = cfg.n_kv_heads * cfg.head_dim
    n_kv = sum(1 for k in _blocks(cfg) if k in ATTN_KINDS)
    keys = jax.random.split(key, n_kv)
    adapters = []
    for k in keys:
        m = jax.random.normal(k, (feat, rank), dtype)
        q, _ = jnp.linalg.qr(m)
        adapters.append(q[:, :rank])
    new = dict(params)
    new["kvswap_adapters"] = adapters
    return new


# --------------------------------------------------------------------------
# cache
# --------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, *, dtype=jnp.float32,
               kvswap: KVSwapServeConfig | None = None):
    if not _is_whisper(cfg) and (cfg.window_layers or cfg.nope_layers or cfg.router_before_attn):
        raise NotImplementedError(
            f"{cfg.name}: this path serves full rotary attention with the router after it; "
            "windowed, NoPE and router-first layers are served by repro.core.engine")
    layers = []
    for kind in _blocks(cfg):
        if kind in ATTN_KINDS:
            ent = {
                "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
            }
            if kvswap is not None:
                ent["k_lr"] = jnp.zeros((batch, max_len, kvswap.rank), dtype)
                if kvswap.rolling:
                    ent["rb_k"] = jnp.zeros((batch, kvswap.rb_len,
                                             cfg.n_kv_heads, cfg.head_dim), dtype)
                    ent["rb_v"] = jnp.zeros_like(ent["rb_k"])
            layers.append(ent)
        elif kind == "mamba2":
            di = cfg.ssm_expand * cfg.d_model
            layers.append({
                "conv": jnp.zeros((batch, di + 2 * cfg.ssm_state, 3), dtype),
                "ssm": jnp.zeros((batch, di // 64, 64, cfg.ssm_state), dtype),
            })
        elif kind == "mlstm":
            hd = cfg.d_model // cfg.n_heads
            layers.append({
                "c": jnp.zeros((batch, cfg.n_heads, hd, hd), dtype),
                "n": jnp.zeros((batch, cfg.n_heads, hd), dtype),
                "m": jnp.full((batch, cfg.n_heads), -1e30, dtype),
            })
        elif kind == "slstm":
            hd = cfg.d_model // cfg.n_heads
            layers.append({
                "c": jnp.zeros((batch, cfg.n_heads, hd), dtype),
                "n": jnp.zeros((batch, cfg.n_heads, hd), dtype),
                "h": jnp.zeros((batch, cfg.n_heads, hd), dtype),
                "m": jnp.full((batch, cfg.n_heads), -1e30, dtype),
            })
        else:
            raise ValueError(kind)
    cache = {"layers": layers, "length": jnp.int32(0)}
    if kvswap is not None and kvswap.rolling:
        cache["main_len"] = jnp.int32(0)   # tokens flushed into the main cache
    return cache


# --------------------------------------------------------------------------
# attention over the cache
# --------------------------------------------------------------------------

def _full_decode_attn(q, ent, length, k_new, v_new):
    """q [B,H,d]; masked attention over cache[:length] + the new token."""
    b, h, d = q.shape
    hk = ent["k"].shape[2]
    n = ent["k"].shape[1]
    pos = jnp.arange(n)
    mask = (pos < length)[None, :]
    k = L.repeat_kv(ent["k"], h // hk)
    v = L.repeat_kv(ent["v"], h // hk)
    scores = jnp.einsum("bhd,bnhd->bhn", q, k) / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.where(mask[:, None, :], scores, NEG)
    self_score = jnp.einsum("bhd,bhd->bh", q, L.repeat_kv(k_new, h // hk).reshape(b, h, d)) \
        / jnp.sqrt(d).astype(q.dtype)
    all_scores = jnp.concatenate([scores, self_score[..., None]], axis=-1)
    w = jax.nn.softmax(all_scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhn,bnhd->bhd", w[..., :-1], v)
    out = out + w[..., -1:][..., None][:, :, 0, :] * L.repeat_kv(v_new, h // hk).reshape(b, h, d)
    return out


def _kvswap_decode_attn(q, ent, adapter, length, k_new, v_new, scfg: KVSwapServeConfig,
                        n_kv_heads: int, main_len=None, q_pred=None):
    """Grouped low-rank selection + gathered attention (Eq. 1 / §3.3).

    With ``scfg.rolling``, selection covers only the flushed prefix
    (``main_len`` tokens) and the rolling buffer's recent tokens are always
    attended (§3.4.1) — identical semantics to the disk engine.

    ``q_pred`` is the query used for *scoring* only (cross-layer prediction:
    projected from the previous layer's input); attention itself always uses
    the true ``q``.  Defaults to ``q`` ("self" prediction).
    """
    b, h, d = q.shape
    g, m = scfg.group_size, scfg.n_select
    n = ent["k"].shape[1]
    n_groups = n // g
    flushed = length if main_len is None else main_len
    if q_pred is None:
        q_pred = q

    # Eq. 1: low-rank queries per head, shared-K-head adapter slices
    a3 = adapter.reshape(n_kv_heads, d, -1)            # [Hk, d, r]
    a_h = jnp.repeat(a3, h // n_kv_heads, axis=0)      # [H, d, r]
    q_lr = jnp.einsum("bhd,hdr->bhr", q_pred, a_h)     # [B,H,r]
    scores = jnp.einsum("bhr,bnr->bn", q_lr, ent["k_lr"])  # head-summed
    pos = jnp.arange(n)
    scores = jnp.where((pos < flushed)[None, :], scores, NEG)
    gsc = scores[:, : n_groups * g].reshape(b, n_groups, g).max(axis=-1)
    top_sc, gids = jax.lax.top_k(gsc, min(m, n_groups))     # [B,M]
    sel_valid = top_sc > NEG / 2

    tok_idx = gids[..., None] * g + jnp.arange(g)[None, None, :]   # [B,M,G]
    tok_idx = tok_idx.reshape(b, -1)                                # [B,M*G]
    k_sel = jnp.take_along_axis(ent["k"], tok_idx[..., None, None], axis=1)
    v_sel = jnp.take_along_axis(ent["v"], tok_idx[..., None, None], axis=1)
    tok_mask = (tok_idx < flushed) & jnp.repeat(sel_valid, g, axis=-1)
    if main_len is not None:
        rb_fill = length - main_len
        rb_mask = (jnp.arange(scfg.rb_len) < rb_fill)[None, :].repeat(b, 0)
        k_sel = jnp.concatenate([k_sel, ent["rb_k"]], axis=1)
        v_sel = jnp.concatenate([v_sel, ent["rb_v"]], axis=1)
        tok_mask = jnp.concatenate([tok_mask, rb_mask], axis=1)
    return L.decode_attention(q, k_sel, v_sel, tok_mask, k_new, v_new)


# --------------------------------------------------------------------------
# prefill + serve_step (generic transformer)
# --------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens, cache, *, kvswap: KVSwapServeConfig | None = None,
            enc_out=None):
    """Run full attention over the prompt, populate the cache.

    Returns (last-position logits, cache)."""
    from repro.models import transformer as T
    from repro.models import whisper as W

    b, s = tokens.shape
    positions = jnp.arange(s)[None, :].repeat(b, axis=0)
    blocks = _blocks(cfg)
    kv_idx = 0
    if _is_whisper(cfg):
        x = params["embed"][tokens] + W.sinusoid_positions(positions, cfg.d_model)
        ckv = W.cross_kv(params, cfg, enc_out)
    else:
        x = params["embed"][tokens]
    layers = list(cache["layers"])
    for i, kind in enumerate(blocks):
        if kind in ATTN_KINDS:
            if _is_whisper(cfg):
                blk = params["dec_blocks"][i]
                h = L.layernorm(blk["ln1"], x)
                q, k, v = W._proj_qkv(blk["attn"], h, cfg)
                o = L.causal_attention(q, k, v)
                x = x + o.reshape(b, s, -1) @ blk["attn"]["wo"]
                ck, cv = ckv[i]
                hc = L.layernorm(blk["ln_cross"], x)
                qc = (hc @ blk["cross"]["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
                oc = L.bidirectional_attention(qc, ck, cv)
                x = x + oc.reshape(b, s, -1) @ blk["cross"]["wo"]
                x = x + L.gelu_mlp(blk["mlp"], L.layernorm(blk["ln_mlp"], x))
            else:
                x, _, (k, v) = T.block_forward(params, cfg, i, x, positions, return_kv=True)
            ent = dict(layers[i])
            ent["k"] = jax.lax.dynamic_update_slice(ent["k"], k.astype(ent["k"].dtype), (0, 0, 0, 0))
            ent["v"] = jax.lax.dynamic_update_slice(ent["v"], v.astype(ent["v"].dtype), (0, 0, 0, 0))
            if kvswap is not None:
                a = params["kvswap_adapters"][kv_idx]
                klr = k.reshape(b, s, -1) @ a
                ent["k_lr"] = jax.lax.dynamic_update_slice(
                    ent["k_lr"], klr.astype(ent["k_lr"].dtype), (0, 0, 0))
            layers[i] = ent
            kv_idx += 1
        else:
            x, _, st = T.block_forward(params, cfg, i, x, positions)
            layers[i] = st
    if _is_whisper(cfg):
        x = L.layernorm(params["final_norm"], x)
        logits = x[:, -1] @ params["embed"].T
    else:
        x = L.rmsnorm(params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x[:, -1] @ head
    new_cache = {"layers": layers, "length": jnp.int32(s)}
    if kvswap is not None and kvswap.rolling:
        new_cache["main_len"] = jnp.int32(s)  # whole prompt lives in main cache
    return logits, new_cache


def serve_step(params, cfg, tokens, cache, *, kvswap: KVSwapServeConfig | None = None,
               enc_out=None):
    """One decode step.  ``tokens [B, 1]`` → ``(logits [B, V], new cache)``.

    Jittable / pjit-lowerable: all shapes static, cache updated functionally.
    """
    from repro.models import whisper as W

    b = tokens.shape[0]
    length = cache["length"]
    pos = jnp.full((b,), length, jnp.int32)
    blocks = _blocks(cfg)
    whisper = _is_whisper(cfg)
    if whisper:
        x = params["embed"][tokens[:, 0]] + W.sinusoid_positions(pos, cfg.d_model)
        ckv = W.cross_kv(params, cfg, enc_out)
    else:
        x = params["embed"][tokens[:, 0]]
    layers = list(cache["layers"])
    kv_idx = 0
    x_prev = x   # input to the previous block (cross-layer prediction source)
    for i, kind in enumerate(blocks):
        x_in = x
        if kind in ATTN_KINDS:
            if whisper:
                blk = params["dec_blocks"][i]
                nb_norm = lambda t: L.layernorm(blk["ln1"], t)
                attn_p = blk["attn"]
            else:
                from repro.models.transformer import _attn_params
                nb, attn_p, mlp_holder = _attn_params(params, cfg, i)
                nb_norm = lambda t: L.rmsnorm(nb["attn_norm"], t)

            def _q_of(t):
                """Layer i's query projection of an arbitrary residual input."""
                qq = (nb_norm(t) @ attn_p["wq"]).reshape(b, cfg.n_heads, cfg.head_dim)
                if not whisper:
                    if cfg.qk_norm:
                        qq = L.rmsnorm(attn_p["q_norm"], qq)
                    qq = L.apply_rope(qq[:, None], pos[:, None], cfg.rope_theta)[:, 0]
                return qq

            h = nb_norm(x)
            q = (h @ attn_p["wq"]).reshape(b, cfg.n_heads, cfg.head_dim)
            k_new = (h @ attn_p["wk"]).reshape(b, cfg.n_kv_heads, cfg.head_dim)
            v_new = (h @ attn_p["wv"]).reshape(b, cfg.n_kv_heads, cfg.head_dim)
            if not whisper:
                if cfg.qk_norm:
                    q = L.rmsnorm(attn_p["q_norm"], q)
                    k_new = L.rmsnorm(attn_p["k_norm"], k_new)
                q = L.apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
                k_new = L.apply_rope(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            ent = layers[i]
            rolling = kvswap is not None and kvswap.rolling
            if kvswap is not None:
                # §3.3: score from the previous block's input so the group
                # gather carries no dependence on this step's earlier layers
                q_pred = (_q_of(x_prev)
                          if kvswap.predict_from == "prev" and i > 0 else None)
                o = _kvswap_decode_attn(q, ent, params["kvswap_adapters"][kv_idx],
                                        length, k_new, v_new, kvswap, cfg.n_kv_heads,
                                        main_len=cache["main_len"] if rolling else None,
                                        q_pred=q_pred)
            else:
                o = _full_decode_attn(q, ent, length, k_new, v_new)
            x = x + o.reshape(b, -1) @ attn_p["wo"]
            # append the new token's KV
            ent = dict(ent)
            if rolling:
                # §3.4.1: append into the small rolling buffer; the
                # seq-sharded main cache is untouched until flush_rolling.
                rb_fill = length - cache["main_len"]
                ent["rb_k"] = jax.lax.dynamic_update_slice(
                    ent["rb_k"], k_new[:, None].astype(ent["rb_k"].dtype),
                    (0, rb_fill, 0, 0))
                ent["rb_v"] = jax.lax.dynamic_update_slice(
                    ent["rb_v"], v_new[:, None].astype(ent["rb_v"].dtype),
                    (0, rb_fill, 0, 0))
            else:
                ent["k"] = jax.lax.dynamic_update_slice(
                    ent["k"], k_new[:, None].astype(ent["k"].dtype), (0, length, 0, 0))
                ent["v"] = jax.lax.dynamic_update_slice(
                    ent["v"], v_new[:, None].astype(ent["v"].dtype), (0, length, 0, 0))
                if kvswap is not None:
                    a = params["kvswap_adapters"][kv_idx]
                    klr_new = k_new.reshape(b, 1, -1) @ a
                    ent["k_lr"] = jax.lax.dynamic_update_slice(
                        ent["k_lr"], klr_new.astype(ent["k_lr"].dtype), (0, length, 0))
            layers[i] = ent
            if whisper:
                blk = params["dec_blocks"][i]
                ck, cv = ckv[i]
                hc = L.layernorm(blk["ln_cross"], x)
                qc = (hc @ blk["cross"]["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
                oc = L.bidirectional_attention(qc, ck, cv)[:, 0]
                x = x + oc.reshape(b, -1) @ blk["cross"]["wo"]
                x = x + L.gelu_mlp(blk["mlp"], L.layernorm(blk["ln_mlp"], x))
            else:
                blk = params["blocks"][i]
                h2 = L.rmsnorm(mlp_holder["mlp_norm"], x)
                if kind == "moe_attn":
                    y, _ = L.moe(blk["moe"], h2[:, None], top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.moe_capacity_factor)
                    y = y[:, 0]
                else:
                    y = L.swiglu(mlp_holder["mlp"], h2)
                x = x + y
            kv_idx += 1
        else:
            blk = params["blocks"][i]
            h = L.rmsnorm(blk["norm"], x)
            if kind == "mamba2":
                y, st = S.mamba2_step(blk["mamba"], h, layers[i])
            elif kind == "mlstm":
                y, st = S.mlstm_step(blk["mlstm"], h, layers[i])
            else:
                y, st = S.slstm_step(blk["slstm"], h, layers[i])
            x = x + y
            layers[i] = st
        x_prev = x_in
    if whisper:
        x = L.layernorm(params["final_norm"], x)
        logits = x @ params["embed"].T
    else:
        x = L.rmsnorm(params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
    new_cache = {"layers": layers, "length": length + 1}
    if "main_len" in cache:
        new_cache["main_len"] = cache["main_len"]
    return logits, new_cache


def flush_rolling(params, cfg, cache, kvswap: KVSwapServeConfig):
    """Merge full rolling buffers into the (seq-sharded) main cache.

    Host loop calls this once every ``kvswap.rb_len`` decode steps — the
    amortized cost of the big-cache update the hot path avoids.  Also appends
    the flushed group's compressed keys to ``k_lr`` (engine §3.4.1 parity).
    """
    main_len = cache["main_len"]
    layers = list(cache["layers"])
    kv_idx = 0
    for i, kind in enumerate(_blocks(cfg)):
        if kind not in ATTN_KINDS:
            continue
        ent = dict(layers[i])
        ent["k"] = jax.lax.dynamic_update_slice(
            ent["k"], ent["rb_k"].astype(ent["k"].dtype), (0, main_len, 0, 0))
        ent["v"] = jax.lax.dynamic_update_slice(
            ent["v"], ent["rb_v"].astype(ent["v"].dtype), (0, main_len, 0, 0))
        a = params["kvswap_adapters"][kv_idx]
        b = ent["rb_k"].shape[0]
        klr = ent["rb_k"].reshape(b, kvswap.rb_len, -1) @ a
        ent["k_lr"] = jax.lax.dynamic_update_slice(
            ent["k_lr"], klr.astype(ent["k_lr"].dtype), (0, main_len, 0))
        layers[i] = ent
        kv_idx += 1
    return {"layers": layers, "length": cache["length"],
            "main_len": main_len + kvswap.rb_len}
