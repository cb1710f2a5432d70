"""The dense GQA family: a decoder of Llama-style blocks (grouped-query
attention, optional per-head RMSNorm on queries and keys, rotary
embedding, SwiGLU MLP), every layer alike.

A family is what ``bench/`` knows of a model's architecture, found by the
``family`` a configuration names (``spec.family``):

    model(cfg)                      the adapter the engine serves
    params(cfg)                     weights on the device, from weights.seed
    calibration_layer(cfg)          the KV layer whose K fits the adapter
    decode_token_flops(cfg, n)      operations to decode one token that
                                    attends ``n`` earlier tokens per layer
    decode_programs(cfg, batch, context, itemsize)
                                    {jitted decode program: (operations,
                                    least HBM bytes) of one call}

Each layer runs once per decode step.  A program that layers of different
cost share under one name (``decode_block`` is jitted with a static
``layer``, so dense, expert and windowed layers all appear in a trace as
``jit_decode_block``) reports the mean cost of one call over those layers.

Operations and bytes are computed from shapes alone: a matmul of [m, k] by
[k, n] is 2*m*k*n operations, and bytes are the least a call must move
through HBM: every weight it reads once, the K/V context it attends over,
and its activations in and out.

The weights are made on the device in one jitted call from the
configuration's own ``weights.seed``, in the layout
``repro.models.transformer`` serves.  A run's ``--seed`` draws only what
its traffic kind draws, so every seed serves the same model.

I.i.d. Gaussian weights give every key a score of about N(0, 1) against
every query, over thousands of keys: attention is flat, and K is full rank.
KVSwap's premise (a low-rank K, and attention that a few groups of tokens
dominate) then does not hold, so its top-M selection is an arbitrary
subset and no reference can say what the served tokens should be.  These
weights are random but shaped so that the premise holds approximately, as
it does in trained models, with each approximation a knob of the
configuration's ``weights`` (listed with its reason under ``assumed``):

* Queries and keys read the first ``qk_read_dims`` features of the
  residual stream.  The embedding writes them; each attention and MLP
  sublayer adds ``read_write_share`` of the embedding's RMS there, so the
  query predicted from the previous layer's input is close to, and not
  the same as, the true query.
* Queries and keys use the ``2 * qk_rope_pairs`` slowest rotary features
  of each head.  Every KV head's key is its own complex-linear map of a
  latent of ``len(key_latent_scales)`` complex numbers per rotary pair,
  the same latent for all heads, so rotation keeps K in the latent's span.
  The first latents per pair (scale 1) fill the adapter's rank; later
  ones, at smaller scales, lie outside it, so the rank-r prediction
  misses part of each score.  The per-head maps are shared across layers
  (the program fits one adapter for all layers, from layer 0).
* Each query head and each KV head has its own projection: a share
  ``head_agreement`` of its variance is common to all heads, the rest its
  own, so heads rank keys alike but not the same, and the head-summed
  selection can miss a head's best keys.
* Each token embedding has a component shared by all tokens (share
  ``shared_query_share`` of its read features) that the key latent
  cancels, so successive queries favour the same keys without a common
  score offset.  This sets how much one step's selection overlaps the
  next one's; the run reports that overlap as ``select_overlap_share``.
* The token-specific part of a score spreads by ``score_std`` across keys,
  so the top groups hold all but a small share of each head's attention.
* Value, output and MLP weights are dense Gaussian; each sublayer adds an
  update of about ``update_rms`` to a stream whose embedding part has RMS
  ``embed_rms``, so with a tied head the next token is not the current one.

Every matrix keeps its published shape, so the program does the same work.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def qk_dims(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """Head features queries and keys use: the slowest rotary pairs, as the
    (first, second) halves that rotate together."""
    hd = cfg["head_dim"]
    p = cfg["weights"]["qk_rope_pairs"]
    lo = np.arange(hd // 2 - p, hd // 2)
    return lo, lo + hd // 2


def params_fn(cfg: dict):
    """The jitted ``key -> params`` function of one configuration."""
    d = cfg["hidden_size"]
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f, vocab = cfg["intermediate_size"], cfg["vocab_size"]
    n_layers = cfg["num_hidden_layers"]
    qk_norm = cfg["qk_norm"]
    tied = cfg["tie_word_embeddings"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    w = cfg["weights"]
    t = w["qk_read_dims"]
    lo, hi = qk_dims(cfg)
    n_pairs = lo.size
    lam = np.asarray(w["key_latent_scales"], np.float32)
    lam = lam / np.sqrt(np.sum(lam * lam))       # keys keep unit variance per feature
    kappa = w["head_agreement"]
    beta = w["shared_query_share"]
    sigma = w["score_std"]
    s_e, a, z = w["embed_rms"], w["update_rms"], w["logit_std"]
    write = w["read_write_share"] * s_e / a      # read-feature share of an update
    keep = math.sqrt(1.0 - beta * beta)
    f32 = jnp.float32

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)

    def mixed(k_shared, k_own, shape, heads):
        # [heads, *shape]: a share kappa of the variance common to all heads
        shared = jax.random.normal(k_shared, shape, f32)
        own = jax.random.normal(k_own, (heads,) + shape, f32)
        return math.sqrt(kappa) * shared[None] + math.sqrt(1.0 - kappa) * own

    def place(blocks):
        # [heads, t, n_pairs] real and imaginary halves -> [d, heads * hd]
        re, im = blocks
        heads = re.shape[0]
        out = jnp.zeros((heads, d, hd), f32)
        out = out.at[:, :t, lo].set(re).at[:, :t, hi].set(im)
        return out.transpose(1, 0, 2).reshape(d, heads * hd).astype(dtype)

    def build(key):
        keys = iter(jax.random.split(key, 5 + 8 * n_layers))
        shared = jax.random.normal(next(keys), (t,), f32)
        embed = normal(next(keys), (vocab, d), s_e)
        embed = embed.at[:, :t].set((beta * s_e * shared).astype(dtype) + keep * embed[:, :t])
        unit = shared / jnp.linalg.norm(shared)
        # each KV head's complex coefficients on the shared key latent, the
        # same in every layer: [nkv, pairs, latents] (real, imaginary)
        coef = mixed(next(keys), next(keys), (n_pairs, lam.size, 2), nkv) / math.sqrt(2.0)
        cr, ci = coef[..., 0] * lam, coef[..., 1] * lam
        blocks = []
        for layer in range(n_layers):
            if qk_norm:
                # rmsnorm fixes |q| and |k|; the norm scales set the spread
                cq = ck = 1.0 / math.sqrt(t)
                gamma = math.sqrt(sigma / (2.0 * keep))
            else:
                # |q|, |k| follow the stream's RMS at this layer's input
                rms = math.sqrt(s_e ** 2 + 2 * layer * a ** 2)
                rho = math.sqrt(2.0 * sigma)
                cq = rho * rms / (s_e * math.sqrt(t))
                ck = cq / keep
            # query heads: their own map of the read features, per layer
            q = mixed(next(keys), next(keys), (t, 2 * n_pairs), nh) * cq
            # key latent of this layer, blind to the shared direction:
            # [t, pairs, latents] (real, imaginary)
            u = jax.random.normal(next(keys), (t, n_pairs, lam.size, 2), f32)
            u = u - unit[:, None, None, None] * jnp.einsum("t,t...->...", unit, u)[None]
            ur, ui = u[..., 0], u[..., 1]
            # complex product latent * coefficient, summed over latents
            k_re = jnp.einsum("tpm,gpm->gtp", ur, cr) - jnp.einsum("tpm,gpm->gtp", ui, ci)
            k_im = jnp.einsum("tpm,gpm->gtp", ur, ci) + jnp.einsum("tpm,gpm->gtp", ui, cr)
            attn = {
                "wq": place((q[..., :n_pairs], q[..., n_pairs:])),
                "wk": place((k_re * ck, k_im * ck)),
                "wv": normal(next(keys), (d, nkv * hd), 1.0 / math.sqrt(d)),
                "wo": normal(next(keys), (nh * hd, d), a / math.sqrt(nh * hd))
                .at[:, :t].multiply(jnp.asarray(write, dtype)),
            }
            if qk_norm:
                attn["q_norm"] = {"scale": jnp.full((hd,), gamma, dtype)}
                attn["k_norm"] = {"scale": jnp.full((hd,), gamma, dtype)}
            mlp = {
                "w_gate": normal(next(keys), (d, f), 1.0 / math.sqrt(d)),
                "w_up": normal(next(keys), (d, f), 1.0 / math.sqrt(d)),
                # silu(g) * u has an RMS of about 0.6 for unit-normal g, u
                "w_down": normal(next(keys), (f, d), a / (0.6 * math.sqrt(f)))
                .at[:, :t].multiply(jnp.asarray(write, dtype)),
            }
            blocks.append({"attn_norm": {"scale": jnp.ones((d,), dtype)}, "attn": attn,
                           "mlp_norm": {"scale": jnp.ones((d,), dtype)}, "mlp": mlp})
        params = {"embed": embed, "blocks": blocks,
                  "final_norm": {"scale": jnp.ones((d,), dtype)}}
        if not tied:
            params["lm_head"] = normal(next(keys), (d, vocab), z / math.sqrt(d))
        return params

    return jax.jit(build)


def params(cfg: dict):
    """Params for ``cfg`` from its ``weights.seed``, on the default device."""
    return jax.block_until_ready(params_fn(cfg)(seed_key(cfg["weights"]["seed"])))


def model(cfg: dict):
    from repro.models.transformer import ModelConfig, TransformerAdapter

    return TransformerAdapter(ModelConfig(
        name=cfg["name"], arch_type="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], qk_norm=cfg["qk_norm"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"]))


def calibration_layer(cfg: dict) -> int:
    """Every layer's K comes from the same per-head maps (see above)."""
    return 0


def layer_matmul_params(cfg: dict) -> int:
    """Weights one layer multiplies by: Q, K, V, O and the three SwiGLU
    matrices."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv, f = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["intermediate_size"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f


def layer_params(cfg: dict) -> int:
    """Every weight of a layer: the matmuls plus the norm scales."""
    extra = 2 * cfg["hidden_size"] + (2 * cfg["head_dim"] if cfg["qk_norm"] else 0)
    return layer_matmul_params(cfg) + extra


def attention_flops(cfg: dict, queries: int, keys: int) -> int:
    """Scores and the weighted sum: two [H, hd] x [hd, keys] products per query."""
    return 4 * queries * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def decode_block_flops(cfg: dict, batch: int, context: int) -> int:
    """One call of the jitted decode layer: ``batch`` tokens, each attending
    ``context`` gathered tokens plus itself."""
    return 2 * batch * layer_matmul_params(cfg) + attention_flops(cfg, batch, context + 1)


def decode_block_bytes(cfg: dict, batch: int, context: int, itemsize: int) -> int:
    """Least HBM traffic of that call: the layer's weights, the gathered K
    and V, the hidden state in and out, and the new K and V out."""
    kv_row = cfg["num_key_value_heads"] * cfg["head_dim"]
    weights = layer_params(cfg) * itemsize
    context_kv = 2 * batch * context * kv_row * itemsize
    activations = 2 * batch * cfg["hidden_size"] * itemsize + 2 * batch * kv_row * itemsize
    return weights + context_kv + activations


def decode_programs(cfg: dict, batch: int, context: int, itemsize: int) -> dict:
    """Every layer runs ``decode_block``, jitted as ``jit_decode_block``."""
    return {"jit_decode_block": (decode_block_flops(cfg, batch, context),
                                 decode_block_bytes(cfg, batch, context, itemsize))}


def decode_token_flops(cfg: dict, attended: int) -> int:
    """Every layer, then the output head."""
    per_layer = 2 * layer_matmul_params(cfg) + attention_flops(cfg, 1, attended + 1)
    return cfg["num_hidden_layers"] * per_layer + 2 * cfg["hidden_size"] * cfg["vocab_size"]
