"""A/B: GSPMD-auto vs explicit shard_map sequence-parallel KVSwap attention.

Lowers the 32-layer long_500k attention stack (llama3-8b dims) both ways on
the 16×16 mesh and compares per-chip collective bytes — the explicit
flash-decoding combine moves only [B,H] partials per shard per layer.

    PYTHONPATH=src python -m benchmarks.shardmap_ab
"""

import os

if __name__ == "__main__":  # device count must be set before jax init
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.common import Timer, emit

B, H, HK, D, N, R, G, M, LAYERS = 1, 32, 8, 128, 524288, 64, 4, 100, 32


def build_inputs(mesh, seq_axes):
    kv_shard = NamedSharding(mesh, P(None, seq_axes, None, None))
    lr_shard = NamedSharding(mesh, P(None, seq_axes, None))
    rep = NamedSharding(mesh, P())
    sds = jax.ShapeDtypeStruct
    args = dict(
        q=sds((B, H, D), jnp.bfloat16),
        q_lr=sds((B, H, R), jnp.bfloat16),
        k_lr=sds((B, N, R), jnp.bfloat16),
        k=sds((B, N, HK, D), jnp.bfloat16),
        v=sds((B, N, HK, D), jnp.bfloat16),
        k_new=sds((B, HK, D), jnp.bfloat16),
        v_new=sds((B, HK, D), jnp.bfloat16),
        length=sds((), jnp.int32),
    )
    shards = dict(q=rep, q_lr=rep, k_lr=lr_shard, k=kv_shard, v=kv_shard,
                  k_new=rep, v_new=rep, length=rep)
    return args, shards


def gspmd_stack(q, q_lr, k_lr, k, v, k_new, v_new, length):
    """take_along_axis formulation; GSPMD chooses the collectives."""
    out = q
    for _ in range(LAYERS):
        scores = jnp.einsum("bhr,bnr->bn", q_lr, k_lr)
        pos = jnp.arange(N)
        scores = jnp.where((pos < length)[None], scores, -1e30)
        gsc = scores.reshape(B, N // G, G).max(-1)
        _, gids = jax.lax.top_k(gsc, M)
        tok = (gids[..., None] * G + jnp.arange(G)).reshape(B, -1)
        k_sel = jnp.take_along_axis(k, tok[..., None, None], axis=1)
        v_sel = jnp.take_along_axis(v, tok[..., None, None], axis=1)
        mask = tok < length
        from repro.models.layers import decode_attention
        out = out + decode_attention(q, k_sel, v_sel, mask, k_new, v_new)
    return out


def main() -> str:
    from repro.launch.dryrun import parse_collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.serving.distributed import make_seqshard_decode_attn

    if len(jax.devices()) < 256:
        emit("shardmap_ab", 0, "SKIPPED (needs 512 forced host devices)")
        return "skipped"
    mesh = make_production_mesh()
    results = {}
    with Timer() as t:
        # A: GSPMD auto
        args, shards = build_inputs(mesh, ("data",))
        with mesh:
            comp = jax.jit(gspmd_stack, in_shardings=tuple(shards.values())) \
                .lower(*args.values()).compile()
        results["gspmd"] = parse_collective_bytes(comp.as_text())["total"]

        # B: explicit shard_map flash-decoding combine
        with mesh:
            attn = make_seqshard_decode_attn(mesh, axis="data", group_size=G,
                                             n_select=M, n_kv_heads=HK)

            def stack(q, q_lr, k_lr, k, v, k_new, v_new, length):
                out = q
                for _ in range(LAYERS):
                    out = out + attn(q, q_lr, k_lr, k, v, k_new, v_new, length)
                return out

            comp = jax.jit(stack, in_shardings=tuple(shards.values())) \
                .lower(*args.values()).compile()
        results["shard_map"] = parse_collective_bytes(comp.as_text())["total"]

    ratio = results["gspmd"] / max(results["shard_map"], 1)
    print(f"collective bytes/chip: gspmd={results['gspmd']:.3e} "
          f"shard_map={results['shard_map']:.3e} ({ratio:.1f}x)")
    emit("shardmap_ab", t.us,
         f"gspmd={results['gspmd']:.2e}B shard_map={results['shard_map']:.2e}B "
         f"reduction={ratio:.1f}x")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
