"""The SmallThinker family: every layer an expert layer (64 ReGLU experts,
6 active, routed from the layer's input before attention); three layers
of four attend a window with rotary embedding, the fourth every earlier
token with none (NoPE).  Which published layers a configuration keeps is
its ``published_layers``, read against the published
``sliding_window_layout`` and ``rope_layout``.

The interface is ``dense_gqa``'s (see there).  On the served path the full
layers are KVSwap layers, run by ``decode_block`` (jitted as
``jit_decode_block``) over the M·G + G gathered tokens, and the windowed
layers run ``decode_window_block`` (``jit_decode_window_block``) over
their device ring of ``sliding_window_size`` tokens: every row of a cell
of this family holds more than a window.

Expert layers are counted at the experts a decode step reads: ``batch``
rows of ``k`` of ``E`` experts each under uniform routing touch
``E·(1 − (1 − k/E)^batch)`` distinct experts, whose weights are the
least bytes; operations are the routed ones, ``k`` experts a token.

Weights are made as ``dense_gqa`` makes them (its ``params_fn`` builds the
embedding, head and attention), so KVSwap's premise holds on the full
layer as it does there: per-head keys from one shared real latent that
fills rank ``r``, with a tail outside it; the NoPE layer reads the same
features without rotating them.  The experts are added here, at their
published shapes, with two more knobs under ``weights``:

* ``router_std``: the router's logits spread by this much across experts
  (the layer's normed input has RMS 1), so the top-6 gates are uneven but
  no expert dominates.
* ``gate_norm``: the RMS norm of the renormalised top-6 gates at that
  spread (``sqrt(sum g^2)``, 0.446 by a numpy simulation of 200,000
  tokens at ``router_std`` 1).  The experts' down projections are scaled
  by its inverse, so an expert layer adds an update of about
  ``update_rms``, as a dense MLP does in ``dense_gqa``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import spec

_dense = spec.family("dense_gqa")


def _dense_cfg(cfg: dict) -> dict:
    """The configuration as ``dense_gqa`` reads it: no qk-norm, and an MLP
    of the expert width, which ``params`` replaces by the experts."""
    return dict(cfg, qk_norm=False, intermediate_size=cfg["moe_ffn_hidden_size"])


def layers(cfg: dict) -> tuple[tuple, tuple]:
    """The kept layers that attend a window, and those without rotary
    embedding."""
    pub = cfg["published_layers"]
    window = tuple(i for i, p in enumerate(pub) if cfg["sliding_window_layout"][p])
    nope = tuple(i for i, p in enumerate(pub) if not cfg["rope_layout"][p])
    return window, nope


def model(cfg: dict):
    from repro.models.transformer import ModelConfig, TransformerAdapter

    window, nope = layers(cfg)
    n = cfg["num_hidden_layers"]
    return TransformerAdapter(ModelConfig(
        name=cfg["name"], arch_type="moe", n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_ffn_hidden_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], block_pattern=("moe_attn",) * n,
        n_experts=cfg["moe_num_primary_experts"],
        moe_top_k=cfg["moe_num_active_primary_experts"],
        moe_d_ff=cfg["moe_ffn_hidden_size"], moe_capacity_factor=None,
        moe_act="relu", router_before_attn=True,
        sliding_window=cfg["sliding_window_size"], window_layers=window,
        nope_layers=nope, tie_embeddings=cfg["tie_word_embeddings"],
        source=cfg["source"]))


def experts_fn(cfg: dict):
    """The jitted ``key -> [per-layer expert weights]`` function."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    e = cfg["moe_num_primary_experts"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    w = cfg["weights"]
    t, a = w["qk_read_dims"], w["update_rms"]
    write = w["read_write_share"] * w["embed_rms"] / a   # as dense_gqa's MLP
    # relu(g) * u has an RMS of about 0.71 for unit-normal g, u
    down = a / (math.sqrt(0.5) * math.sqrt(f) * w["gate_norm"])

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)

    def build(key):
        out = []
        for k in jax.random.split(key, cfg["num_hidden_layers"]):
            kr, kg, ku, kd = jax.random.split(k, 4)
            out.append({
                "router": normal(kr, (d, e), w["router_std"] / math.sqrt(d)),
                "w_gate": normal(kg, (e, d, f), 1.0 / math.sqrt(d)),
                "w_up": normal(ku, (e, d, f), 1.0 / math.sqrt(d)),
                "w_down": normal(kd, (e, f, d), down)
                .at[:, :, :t].multiply(jnp.asarray(write, dtype)),
            })
        return out

    return jax.jit(build)


def params(cfg: dict):
    """Params for ``cfg`` from its ``weights.seed``, on the default device."""
    key = _dense.seed_key(cfg["weights"]["seed"])
    p = _dense.params_fn(_dense_cfg(cfg))(key)
    for blk, moe in zip(p["blocks"], experts_fn(cfg)(jax.random.fold_in(key, 1))):
        del blk["mlp"]
        blk["moe"] = moe
    return jax.block_until_ready(p)


def calibration_layer(cfg: dict) -> int:
    """The first full layer: the only kind KVSwap serves."""
    window, _ = layers(cfg)
    return next(i for i in range(cfg["num_hidden_layers"]) if i not in window)


def attention_params(cfg: dict) -> int:
    """Q, K, V and O of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * nh * hd + 2 * d * nkv * hd


def expert_params(cfg: dict) -> int:
    """Gate, up and down of one expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def routed_experts(cfg: dict, batch: int) -> float:
    """Distinct experts ``batch`` tokens touch under uniform routing."""
    e, k = cfg["moe_num_primary_experts"], cfg["moe_num_active_primary_experts"]
    return e * (1.0 - (1.0 - k / e) ** batch)


def token_matmul_flops(cfg: dict) -> int:
    """One token through one layer's matmuls: attention projections, the
    router and its ``k`` experts."""
    k, e = cfg["moe_num_active_primary_experts"], cfg["moe_num_primary_experts"]
    return 2 * (attention_params(cfg) + cfg["hidden_size"] * e + k * expert_params(cfg))


def decode_call(cfg: dict, batch: int, attended: int, itemsize: int) -> tuple[int, float]:
    """Operations and least HBM bytes of one decode call of a layer:
    ``batch`` tokens, each attending ``attended`` tokens (itself included).
    Bytes: the attention weights, the router, the experts the batch
    routes to, the attended K and V, the hidden state in and out and the
    new K and V out."""
    kv_row = cfg["num_key_value_heads"] * cfg["head_dim"]
    d, e = cfg["hidden_size"], cfg["moe_num_primary_experts"]
    flops = batch * token_matmul_flops(cfg) + _dense.attention_flops(cfg, batch, attended)
    weights = (attention_params(cfg) + d * e + 2 * d
               + routed_experts(cfg, batch) * expert_params(cfg)) * itemsize
    context_kv = 2 * batch * (attended - 1) * kv_row * itemsize
    activations = 2 * batch * d * itemsize + 2 * batch * kv_row * itemsize
    return flops, weights + context_kv + activations


def decode_programs(cfg: dict, batch: int, context: int, itemsize: int) -> dict:
    """The full layers' ``decode_block`` over ``context`` gathered tokens
    and the windowed layers' ``decode_window_block`` over their ring."""
    return {"jit_decode_block": decode_call(cfg, batch, context + 1, itemsize),
            "jit_decode_window_block": decode_call(cfg, batch, cfg["sliding_window_size"],
                                                   itemsize)}


def decode_token_flops(cfg: dict, attended: int) -> int:
    """Every layer (a windowed one attends its window, itself included; a
    full one ``attended`` earlier tokens and itself), then the head."""
    window, _ = layers(cfg)
    total = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        seen = cfg["sliding_window_size"] if i in window else attended + 1
        total += token_matmul_flops(cfg) + _dense.attention_flops(cfg, 1, seen)
    return total
