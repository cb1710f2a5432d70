"""Ahead-of-time compiles for a described TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these catch what interpret mode cannot: block
shapes the Mosaic lowering refuses, in-kernel ops it cannot lay out, and
programs that do not fit.  Only shapes are passed (``jax.eval_shape`` for
the model); nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers that
decided at import whether these tests exist would collect different tests.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Qwen3-8B attention widths (repro.configs.qwen3_8b), engine defaults
B, H, HK, D, R, N, G, M = 2, 32, 8, 128, 64, 8192, 4, 100


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    return jax.jit(functools.partial(fn, **kw)).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_lowrank_group_scores_compiles(one_chip):
    from repro.kernels.lowrank_score import lowrank_group_scores_pallas

    s = functools.partial(_shape, one_chip)
    c = _compile(lowrank_group_scores_pallas, s((B, H, R)), s((B, N, R)),
                 s((B,), jnp.int32), group_size=G, block_n=128 * G)
    assert _has_kernel(c)


def test_fused_predict_compiles(one_chip):
    from repro.kernels.fused_predict import fused_predict_pallas

    s = functools.partial(_shape, one_chip)
    # an engine K_lr length (max_seq 2112) that is not a tile multiple
    c = _compile(fused_predict_pallas, s((B, H, D)), s((HK, D, R)),
                 s((B, 2112, R)), s((B,), jnp.int32), group_size=G, n_select=M)
    assert _has_kernel(c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_attention_compiles(one_chip, dtype):
    from repro.kernels.gather_attention import gather_attention_pallas
    from repro.kernels.ops import gather_attention

    s = functools.partial(_shape, one_chip)
    kv = s((B, HK, 512, D), dtype)
    c = _compile(gather_attention_pallas, s((B, H, D), dtype), kv, kv,
                 s((B, 512), jnp.bool_), block_t=256)
    assert _has_kernel(c)
    # the engine's context: M·G selected tokens + a G-token tail + self
    ctx = s((B, M * G + G + 1, HK, D), dtype)
    c = _compile(gather_attention, s((B, H, D), dtype), ctx, ctx,
                 s((B, M * G + G + 1), jnp.bool_))
    assert _has_kernel(c)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_decode_block_compiles_at_qwen3_8b_widths(one_chip, use_pallas):
    from repro.configs import qwen3_8b
    from repro.models.transformer import TransformerAdapter, init_params

    cfg = dataclasses.replace(qwen3_8b.config(), n_layers=2)
    model = TransformerAdapter(cfg)
    params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    s = functools.partial(_shape, one_chip)
    ctx = s((B, M * G + G, HK, D))
    c = model.decode_block.lower(
        model, params, 1, s((B, cfg.d_model)), s((B,), jnp.int32), ctx, ctx,
        s((B, M * G + G), jnp.bool_), use_pallas=use_pallas).compile()
    assert _has_kernel(c) == use_pallas
    # one layer's float32 weights are the arguments: 0.78 GB of 16 GB
    assert c.memory_analysis().argument_size_in_bytes < 1 << 30


def test_step_end_flush_compiles_at_qwen3_8b_widths(one_chip):
    """The decode step's group flush at the benchmark's shapes: 8 rows, 4 KV
    layers, a 16388-token ``k_lr`` per layer, bfloat16 storage.  ``k_lr`` is
    donated, so the appends are made in place."""
    from repro.core.engine import _flush_groups

    s = functools.partial(_shape, one_chip)
    b, layers, cap = 8, 4, 16388
    k_lr = tuple(s((b, cap, R)) for _ in range(layers))
    tails = tuple(s((b, G, HK, D)) for _ in range(layers))
    c = _flush_groups.lower(k_lr, s((HK * D, R)), s((layers, b), jnp.bool_),
                            s((b,), jnp.int32), tails, tails,
                            dtype=np.dtype(jnp.bfloat16)).compile()
    mem = c.memory_analysis()
    klr_bytes = layers * b * cap * R * 4
    assert mem.alias_size_in_bytes >= klr_bytes
    assert mem.temp_size_in_bytes < klr_bytes // layers


@pytest.mark.parametrize("program", ["decode_block", "decode_window_block"])
def test_expert_decode_programs_compile_at_smallthinker_widths(one_chip, program):
    """The full layer's ``decode_block`` and the windowed layers'
    ``decode_window_block`` at SmallThinker-21B-A3B's widths (64 experts of
    768, 6 active, routed through the grouped-matmul kernel), bfloat16, 8
    rows; the 4096-token rings are donated and written in place."""
    from repro.configs import smallthinker_21b_a3b
    from repro.models.transformer import TransformerAdapter, init_params

    cfg = dataclasses.replace(smallthinker_21b_a3b.config(), n_layers=4,
                              block_pattern=("moe_attn",) * 4,
                              window_layers=(0, 1, 2), nope_layers=(3,))
    model = TransformerAdapter(cfg)
    params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    s = functools.partial(_shape, one_chip)
    b, hk, d = 8, cfg.n_kv_heads, cfg.head_dim
    x, pos = s((b, cfg.d_model), jnp.bfloat16), s((b,), jnp.int32)
    if program == "decode_block":
        ctx = s((b, M * G + G, hk, d), jnp.bfloat16)
        c = model.decode_block.lower(model, params, 3, x, pos, ctx, ctx,
                                     s((b, M * G + G), jnp.bool_)).compile()
    else:
        ring = s((b, cfg.sliding_window, hk, d), jnp.bfloat16)
        c = model.decode_window_block.lower(model, params, 0, x, pos, ring, ring,
                                            s((b,), jnp.bool_)).compile()
        ring_bytes = 2 * b * cfg.sliding_window * hk * d * 2
        assert c.memory_analysis().alias_size_in_bytes >= ring_bytes
    assert _has_kernel(c)
