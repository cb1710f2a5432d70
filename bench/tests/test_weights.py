"""The weight structure at a CPU size: heads have maps of their own, K of
every layer lies close to the adapter's rank, and the weights do not
depend on the run's seed."""

import numpy as np

from bench import spec

DENSE = spec.family("dense_gqa")


def _keys(cfg, params, layer, n=256, seed=0):
    """Post-norm, pre-rotation keys ``[n, kv heads, head dim]`` of ``n``
    random tokens at one layer's input (the embedding, for layer 0)."""
    toks = np.random.default_rng(seed).integers(0, cfg["vocab_size"], n)
    x = np.asarray(params["embed"], np.float32)[toks]
    h = x / np.sqrt(np.mean(x * x, -1, keepdims=True))
    at = params["blocks"][layer]["attn"]
    k = (h @ np.asarray(at["wk"], np.float32)).reshape(n, cfg["num_key_value_heads"], -1)
    return k / np.sqrt(np.mean(k * k, -1, keepdims=True))


def test_heads_have_their_own_maps_inside_one_latent(tiny):
    # four KV heads, so that their joint key features (4 x 8) outnumber
    # the adapter's rank (16), as at the published widths (8 x 32 and 64)
    cfg = dict(tiny("qwen3-8b.longctx-decode").config, num_key_value_heads=4)
    params = DENSE.params(cfg)
    k = _keys(cfg, params, 0)
    # two KV heads read the same tokens differently, but alike
    corr = np.corrcoef(k[:, 0].ravel(), k[:, 1].ravel())[0, 1]
    assert 0.3 < corr < 0.99
    # the joint keys of all heads: the adapter's rank holds nearly all of
    # their energy, and not all of it (the latent's tail lies outside)
    sv = np.linalg.svd(k.reshape(len(k), -1), compute_uv=False) ** 2
    rank = cfg["engine"]["rank"]
    held = sv[:rank].sum() / sv.sum()
    assert 0.9 < held < 0.9999
    q = np.asarray(params["blocks"][0]["attn"]["wq"], np.float32)
    hd = cfg["head_dim"]
    assert not np.allclose(q[:, :hd], q[:, hd:2 * hd])


def test_weights_come_from_the_configuration_alone(tiny):
    cfg = tiny("qwen3-8b.longctx-decode").config
    a = DENSE.params(cfg)["blocks"][1]["attn"]["wk"]
    b = DENSE.params(cfg)["blocks"][1]["attn"]["wk"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
