"""Generic decoder stack covering dense / MoE / SSM / hybrid architectures.

A model is a :class:`ModelConfig` (block pattern + dims) plus a params pytree.
``forward`` runs the full sequence (training / prefill);
:class:`TransformerAdapter` exposes the per-block prefill/decode interface the
KVSwap engine consumes (repro.core.adapter.ModelAdapter).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S

ATTN_KINDS = ("attn", "moe_attn", "shared_attn")
STATE_KINDS = ("mamba2", "mlstm", "slstm")

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    block_pattern: tuple = ()      # per-layer kinds; default: all "attn"
    qk_norm: bool = False
    rope_theta: float = 500000.0
    n_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_capacity_factor: float | None = 1.25   # None: dropless (every assignment)
    moe_act: str = "silu"          # expert gate: "silu" (SwiGLU) | "relu" (ReGLU)
    router_before_attn: bool = False   # router reads the layer's normed input
    sliding_window: int = 0        # keys a windowed layer's query sees, its own included
    window_layers: tuple = ()      # layers that attend only the window
    nope_layers: tuple = ()        # layers without rotary embedding
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_use_pallas: bool = False   # route Mamba2 intra-chunk through Pallas
    tie_embeddings: bool = True
    source: str = ""               # citation for the config

    @property
    def blocks(self) -> tuple:
        return self.block_pattern or ("attn",) * self.n_layers

    @property
    def kv_layers(self) -> tuple:
        return tuple(i for i, k in enumerate(self.blocks) if k in ATTN_KINDS)

    def window(self, layer: int) -> int:
        """Keys ``layer``'s query sees, its own included (0: every earlier one)."""
        return self.sliding_window if layer in self.window_layers else 0


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    n_blocks = len(cfg.blocks)
    keys = jax.random.split(key, n_blocks + 3)
    attn_kw = dict(d_model=cfg.d_model, n_heads=cfg.n_heads,
                   n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                   qk_norm=cfg.qk_norm, dtype=dtype)
    blocks = []
    for i, kind in enumerate(cfg.blocks):
        k = keys[i]
        if kind == "attn":
            ka, km = jax.random.split(k)
            blocks.append({
                "attn_norm": L.init_rmsnorm(cfg.d_model, dtype),
                "attn": L.init_attention(ka, **attn_kw),
                "mlp_norm": L.init_rmsnorm(cfg.d_model, dtype),
                "mlp": L.init_swiglu(km, cfg.d_model, cfg.d_ff, dtype),
            })
        elif kind == "moe_attn":
            ka, km = jax.random.split(k)
            blocks.append({
                "attn_norm": L.init_rmsnorm(cfg.d_model, dtype),
                "attn": L.init_attention(ka, **attn_kw),
                "mlp_norm": L.init_rmsnorm(cfg.d_model, dtype),
                "moe": L.init_moe(km, d_model=cfg.d_model, d_ff=cfg.moe_d_ff,
                                  n_experts=cfg.n_experts, dtype=dtype,
                                  shared_d_ff=cfg.moe_shared_d_ff),
            })
        elif kind == "shared_attn":
            blocks.append({"attn_norm": L.init_rmsnorm(cfg.d_model, dtype)})
        elif kind == "mamba2":
            blocks.append({
                "norm": L.init_rmsnorm(cfg.d_model, dtype),
                "mamba": S.init_mamba2(k, d_model=cfg.d_model, d_state=cfg.ssm_state,
                                       expand=cfg.ssm_expand, dtype=dtype),
            })
        elif kind == "mlstm":
            blocks.append({
                "norm": L.init_rmsnorm(cfg.d_model, dtype),
                "mlstm": S.init_mlstm(k, d_model=cfg.d_model, n_heads=cfg.n_heads, dtype=dtype),
            })
        elif kind == "slstm":
            blocks.append({
                "norm": L.init_rmsnorm(cfg.d_model, dtype),
                "slstm": S.init_slstm(k, d_model=cfg.d_model, n_heads=cfg.n_heads, dtype=dtype),
            })
        else:
            raise ValueError(f"unknown block kind {kind}")
    params = {
        "embed": jax.random.normal(keys[-3], (cfg.vocab_size, cfg.d_model), dtype) * 0.02,
        "blocks": blocks,
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if "shared_attn" in cfg.blocks:
        ka, km = jax.random.split(keys[-2])
        params["shared_attn"] = {
            "attn": L.init_attention(ka, **attn_kw),
            "mlp_norm": L.init_rmsnorm(cfg.d_model, dtype),
            "mlp": L.init_swiglu(km, cfg.d_model, cfg.d_ff, dtype),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[-1], (cfg.d_model, cfg.vocab_size), dtype)
    return params


# --------------------------------------------------------------------------
# full-sequence forward (training / prefill)
# --------------------------------------------------------------------------

def _attn_params(params, cfg: ModelConfig, layer: int):
    kind = cfg.blocks[layer]
    blk = params["blocks"][layer]
    if kind == "shared_attn":
        return blk, params["shared_attn"]["attn"], params["shared_attn"]
    return blk, blk["attn"], blk


def _qkv(params, cfg: ModelConfig, layer: int, h, positions):
    """q, k, v of the normed layer input ``h [B, S, D]`` (rotated unless the
    layer is NoPE), and the router's logits where the router reads ``h``."""
    _, attn_p, _ = _attn_params(params, cfg, layer)
    q, k, v = L.attention_qkv(attn_p, h, positions, n_heads=cfg.n_heads,
                              n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                              rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                              rope=layer not in cfg.nope_layers)
    r = None
    if cfg.blocks[layer] == "moe_attn" and cfg.router_before_attn:
        r = h @ params["blocks"][layer]["moe"]["router"]
    return q, k, v, r


def _finish(params, cfg: ModelConfig, layer: int, x, o, r):
    """The block after attention: ``x`` plus the output projection of ``o``,
    then plus the feed-forward sublayer of the normed stream (routed by
    ``r`` where the router read the layer's input).  Returns (x, aux)."""
    _, attn_p, holder = _attn_params(params, cfg, layer)
    x = x + o.reshape(*x.shape[:-1], cfg.n_heads * cfg.head_dim) @ attn_p["wo"]
    h2 = L.rmsnorm(holder["mlp_norm"], x)
    if cfg.blocks[layer] == "moe_attn":
        y, aux = L.moe(params["blocks"][layer]["moe"], h2, top_k=cfg.moe_top_k,
                       capacity_factor=cfg.moe_capacity_factor,
                       act=getattr(jax.nn, cfg.moe_act), logits=r)
    else:
        y, aux = L.swiglu(holder["mlp"], h2), 0.0
    return x + y, aux


def block_forward(params, cfg: ModelConfig, layer: int, x, positions, state=None,
                  *, return_kv: bool = False):
    """Full-seq forward through one block.  Returns (x, aux, kv_or_state)."""
    kind = cfg.blocks[layer]
    blk = params["blocks"][layer]
    aux = 0.0
    if kind in ATTN_KINDS:
        nb, _, _ = _attn_params(params, cfg, layer)
        h = L.rmsnorm(nb["attn_norm"], x)
        q, k, v, r = _qkv(params, cfg, layer, h, positions)
        o = L.causal_attention(q, k, v, cfg.window(layer))
        x, aux = _finish(params, cfg, layer, x, o, r)
        return x, aux, ((k, v) if return_kv else None)
    # state blocks
    h = L.rmsnorm(blk["norm"], x)
    if kind == "mamba2":
        y, st = S.mamba2_forward(blk["mamba"], h, state,
                                 use_pallas=cfg.ssm_use_pallas)
    elif kind == "mlstm":
        y, st = S.mlstm_forward(blk["mlstm"], h, state)
    else:
        y, st = S.slstm_forward(blk["slstm"], h, state)
    return x + y, aux, st


def forward(params, cfg: ModelConfig, tokens, *, embeddings=None,
            remat: bool = False):
    """Full forward: ``tokens [B, S]`` (or precomputed ``embeddings``) →
    ``(logits [B, S, V], aux_loss)``.

    ``remat=True`` checkpoints each block (recompute activations in the
    backward pass) — the standard training memory/compute trade; cuts the
    live-activation footprint from O(L) blocks to O(1).
    """
    if embeddings is None:
        x = params["embed"][tokens]
    else:
        x = embeddings
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :].repeat(b, axis=0)
    aux_total = 0.0
    for i in range(len(cfg.blocks)):
        if remat:
            def blk(params_, x_, _i=i):
                y, aux, _ = block_forward(params_, cfg, _i, x_, positions)
                return y, aux
            x, aux = jax.checkpoint(blk)(params, x)
        else:
            x, aux, _ = block_forward(params, cfg, i, x, positions)
        aux_total = aux_total + aux
    x = L.rmsnorm(params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, aux_total


# --------------------------------------------------------------------------
# engine adapter
# --------------------------------------------------------------------------

class TransformerAdapter:
    """Implements repro.core.adapter.ModelAdapter for this stack.

    ``n_layers`` as seen by the engine counts **all** blocks, and
    ``layer_kinds`` tells the engine how to serve each: ``"kv"`` (attention
    over every earlier token: KVSwap's disk-backed cache), ``"window"``
    (attention over the last ``window`` tokens, held in a device ring) or
    ``"state"`` (recurrent state: hybrid support).
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_layers = len(cfg.blocks)
        self.n_heads = cfg.n_heads
        self.n_kv_heads = cfg.n_kv_heads
        self.head_dim = cfg.head_dim
        self.d_model = cfg.d_model
        self.d_ff = cfg.d_ff or 4 * cfg.d_model
        self.vocab_size = cfg.vocab_size
        self.window = cfg.sliding_window
        self.layer_kinds = tuple(
            "state" if k not in ATTN_KINDS else "window" if cfg.window(i) else "kv"
            for i, k in enumerate(cfg.blocks))

    # -- embedding / head -------------------------------------------------
    def embed(self, params, tokens):
        return params["embed"][tokens]

    def logits(self, params, x):
        x = L.rmsnorm(params["final_norm"], x)
        head = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        return x @ head

    # -- prefill -----------------------------------------------------------
    def prefill_block(self, params, layer, x, positions):
        x, _, kv = block_forward(params, self.cfg, layer, x, positions, return_kv=True)
        return x, kv[0], kv[1]

    def prefill_state_block(self, params, layer, x, positions):
        x, _, st = block_forward(params, self.cfg, layer, x, positions)
        return x, st

    def prefill_block_with_ctx(self, params, layer, x, positions, k_prefix, v_prefix):
        """Chunked prefill: run only the *suffix* tokens through block
        ``layer``, attending over restored prefix KV plus their own.

        ``x [B, S_suf, D]``, ``positions [B, S_suf]`` (absolute),
        ``k_prefix/v_prefix [B, S_pre, H_kv, d]`` (post-RoPE, as cached;
        a windowed layer needs only the last ``window`` of them).
        Returns ``(x_out [B, S_suf, D], k_suf, v_suf [B, S_suf, H_kv, d])``.

        Deliberately NOT jitted as a whole block: :func:`block_forward` (the
        cold path) runs op-by-op, and whole-block XLA fusion reassociates
        float reductions — the op-by-op chunked path computes the exact same
        score rows, which is what makes warm prefill bit-identical to cold
        (dense MLP blocks; MoE capacity routing sees only the suffix tokens,
        which matches the full forward exactly when no tokens are dropped).
        """
        cfg = self.cfg
        nb, _, _ = _attn_params(params, cfg, layer)
        h = L.rmsnorm(nb["attn_norm"], x)
        q, k, v, r = _qkv(params, cfg, layer, h, positions)
        k_all = jnp.concatenate([k_prefix.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([v_prefix.astype(v.dtype), v], axis=1)
        o = L.chunked_causal_attention(q, k_all, v_all, k_prefix.shape[1],
                                       cfg.window(layer))
        x, _ = _finish(params, cfg, layer, x, o, r)
        return x, k, v

    # -- decode ------------------------------------------------------------
    def gather_context(self, dev_k, dev_v, slots, tail_k, tail_v, tail_fill):
        """Device-resident context assembly (engine ``device_resident=True``).

        Gathers the step's working set out of the persistent device reuse
        mirror by slot permutation plus the device rolling tail (``tail_k/
        tail_v [B, G, H_kv, d]`` with per-row valid counts ``tail_fill
        [B]`` — rows advance independently under continuous batching) — no
        host concat, no full re-upload — and returns the same ``(k_ctx,
        v_ctx, ctx_mask)`` triple :meth:`decode_block` consumes, so the
        decode compute is the *identical* compiled function in both engine
        paths (the bit-identity contract).  An adapter without this method
        makes the engine fall back to host gather.
        """
        return L.gather_slots(dev_k, dev_v, slots, tail_k, tail_v, tail_fill)

    @functools.partial(jax.jit, static_argnames=("self", "layer", "use_pallas"))
    def decode_block(self, params, layer, x, positions, k_ctx, v_ctx, ctx_mask,
                     use_pallas=False):
        cfg = self.cfg
        nb, _, _ = _attn_params(params, cfg, layer)
        h = L.rmsnorm(nb["attn_norm"], x)
        q, k_new, v_new, r = _qkv(params, cfg, layer, h[:, None], positions[:, None])
        q, k_new, v_new = q[:, 0], k_new[:, 0], v_new[:, 0]
        o = L.decode_attention(q, k_ctx, v_ctx, ctx_mask, k_new, v_new,
                               use_pallas=use_pallas)
        x, _ = _finish(params, cfg, layer, x, o, None if r is None else r[:, 0])
        return x, k_new, v_new

    @functools.partial(jax.jit, static_argnames=("self", "layer"),
                       donate_argnames=("ring_k", "ring_v"))
    def decode_window_block(self, params, layer, x, positions, ring_k, ring_v, active):
        """One decode step of a windowed layer, in one program.

        ``ring_k/ring_v [B, W, H_kv, d]`` hold each row's last ``W`` tokens,
        the token at position ``p`` in slot ``p % W``.  The query at
        ``positions`` attends the ring's slots that hold positions inside
        its window, and itself; its K/V then replace the slot's oldest
        token in the donated ring, for ``active`` rows only.  Returns
        ``(x, ring_k, ring_v)``."""
        cfg = self.cfg
        nb, _, _ = _attn_params(params, cfg, layer)
        h = L.rmsnorm(nb["attn_norm"], x)
        q, k_new, v_new, r = _qkv(params, cfg, layer, h[:, None], positions[:, None])
        q, k_new, v_new = q[:, 0], k_new[:, 0], v_new[:, 0]
        w = ring_k.shape[1]
        slot = positions % w
        # slot s holds the latest position p = s (mod W) before the query:
        # before the ring fills, the slots below the query's position; then
        # every slot but the one about to be overwritten (p = query - W)
        s_idx = jnp.arange(w)[None, :]
        mask = jnp.where(positions[:, None] < w, s_idx < positions[:, None],
                         s_idx != slot[:, None])
        o = L.decode_attention(q, ring_k, ring_v, mask, k_new.astype(ring_k.dtype),
                               v_new.astype(ring_v.dtype))
        x, _ = _finish(params, cfg, layer, x, o, None if r is None else r[:, 0])
        rows = jnp.arange(x.shape[0])
        keep = active[:, None, None]
        ring_k = ring_k.at[rows, slot].set(
            jnp.where(keep, k_new.astype(ring_k.dtype), ring_k[rows, slot]))
        ring_v = ring_v.at[rows, slot].set(
            jnp.where(keep, v_new.astype(ring_v.dtype), ring_v[rows, slot]))
        return x, ring_k, ring_v

    @functools.partial(jax.jit, static_argnames=("self", "layer"))
    def decode_state_block(self, params, layer, x, positions, state):
        cfg = self.cfg
        blk = params["blocks"][layer]
        kind = cfg.blocks[layer]
        h = L.rmsnorm(blk["norm"], x)
        if kind == "mamba2":
            y, st = S.mamba2_step(blk["mamba"], h, state)
        elif kind == "mlstm":
            y, st = S.mlstm_step(blk["mlstm"], h, state)
        else:
            y, st = S.slstm_step(blk["slstm"], h, state)
        return x + y, st

    def init_state(self, params, layer, batch):
        kind = self.cfg.blocks[layer]
        blk = params["blocks"][layer]
        if kind == "mamba2":
            return S.mamba2_init_state(blk["mamba"], batch)
        if kind == "mlstm":
            return S.mlstm_init_state(blk["mlstm"], batch)
        if kind == "slstm":
            return S.slstm_init_state(blk["slstm"], batch)
        raise ValueError(f"layer {layer} has no state")

    # -- predictor ---------------------------------------------------------
    @functools.partial(jax.jit, static_argnames=("self", "layer"))
    def predict_query(self, params, layer, x, positions):
        cfg = self.cfg
        nb, attn_p, _ = _attn_params(params, cfg, layer)
        h = L.rmsnorm(nb["attn_norm"], x)
        b = x.shape[0]
        q = (h @ attn_p["wq"]).reshape(b, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = L.rmsnorm(attn_p["q_norm"], q)
        if layer in cfg.nope_layers:
            return q
        return L.apply_rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
