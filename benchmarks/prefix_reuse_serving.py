"""Cross-request prefix reuse: cold vs warm prefill (``src/repro/cache/``).

Two sections:

* ``run_modeled`` — analytic latency for a realistic on-device deployment
  (llama3-8b dims, Jetson Orin compute, int8 KV on disk per §7): cold
  prefill = full-attention compute + KV spill writes; warm prefill =
  sequential restore reads of the cached prefix + the same writes + chunked
  compute of the uncached suffix.  Reported for both NVMe and eMMC specs;
  the headline claim is **warm < 0.5× cold on both**.

* ``run_session`` — a real session through :class:`BatchServer` with a
  persistent :class:`PrefixCache`: flush 1 publishes the system prompt cold,
  flush 2 restores it warm.  Reports the measured cache hit rate and saved
  prefill tokens from ``last_stats`` (tiny model — the *modeled* speedup at
  this scale is compute-poor, which is exactly why the analytic section
  uses deployment dims).

Usage::

    PYTHONPATH=src python -m benchmarks.prefix_reuse_serving [--tiny]
"""

from __future__ import annotations

import argparse

from benchmarks.common import LLAMA3_8B, N_LAYERS
from repro.core.hardware import ORIN, ModelDims, prefill_layer_time
from repro.core.offload import DISKS, DiskSpec


def modeled_prefill_seconds(
    disk: DiskSpec,
    dims: ModelDims,
    n_layers: int,
    *,
    s: int,
    s_cached: int,
    kv_itemsize: int = 1,
    batch: int = 1,
) -> dict:
    """Modeled warm-prefill latency with ``s_cached`` of ``s`` prompt tokens
    restored from the prefix cache (``s_cached=0`` = cold).

    Mirrors the engine's accounting: restore is one sequential run per layer
    of the unique chain (batched rows share the prompt, so it is read once);
    the spill writes cover the full prompt either way; compute covers only
    the uncached suffix, chunked over the restored context.
    """
    ent_layer = 2 * dims.n_kv_heads * dims.head_dim * kv_itemsize  # B/token/layer
    restore = disk.read_time(n_layers * s_cached * ent_layer, n_layers) if s_cached else 0.0
    writes = disk.write_time(batch * n_layers * s * ent_layer, batch * n_layers)
    compute = n_layers * prefill_layer_time(ORIN, dims, n_new=s - s_cached,
                                            n_ctx0=s_cached, batch=batch)
    return {"restore_s": restore, "write_s": writes, "compute_s": compute,
            "total_s": restore + writes + compute}


def run_modeled(*, s: int = 4096, cached_frac: float = 0.875,
                kv_itemsize: int = 1, batch: int = 1) -> dict:
    """Cold vs warm modeled prefill on both disk specs.  Returns the ratios."""
    dims, n_layers = LLAMA3_8B, N_LAYERS["llama3-8b"]
    s_cached = int(s * cached_frac)
    fmt = "int8" if kv_itemsize == 1 else f"{8 * kv_itemsize}-bit"
    print(f"# llama3-8b dims, S={s}, cached={s_cached} ({cached_frac:.1%}), "
          f"disk KV {fmt}, batch={batch}")
    print("disk,cold_ms,warm_ms,warm/cold,restore_ms,write_ms,suffix_compute_ms")
    ratios = {}
    for name, disk in DISKS.items():
        cold = modeled_prefill_seconds(disk, dims, n_layers, s=s, s_cached=0,
                                       kv_itemsize=kv_itemsize, batch=batch)
        warm = modeled_prefill_seconds(disk, dims, n_layers, s=s, s_cached=s_cached,
                                       kv_itemsize=kv_itemsize, batch=batch)
        ratio = warm["total_s"] / cold["total_s"]
        ratios[name] = ratio
        print(f"{name},{cold['total_s'] * 1e3:.1f},{warm['total_s'] * 1e3:.1f},"
              f"{ratio:.3f},{warm['restore_s'] * 1e3:.1f},"
              f"{warm['write_s'] * 1e3:.1f},{warm['compute_s'] * 1e3:.1f}")
    return ratios


def run_session(*, sys_len: int = 48, user_len: int = 8, max_new: int = 4,
                batch: int = 2) -> dict:
    """Drive a real BatchServer session: cold flush, then a warm one."""
    import jax
    import numpy as np

    from repro.cache import PrefixCache, PrefixCacheConfig
    from repro.core.engine import EngineConfig
    from repro.models.transformer import (ModelConfig, TransformerAdapter,
                                          init_params)
    from repro.serving.scheduler import BatchServer

    cfg = ModelConfig(name="bench", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=211)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((128, cfg.n_kv_heads, cfg.head_dim))
    max_seq = sys_len + user_len + max_new + 16
    ecfg = EngineConfig(group_size=4, n_select=max_seq // 4, rank=16,
                        reuse_capacity=max_seq // 4, max_seq=max_seq,
                        predict_from="self")
    stats = {}
    with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
        srv = BatchServer(TransformerAdapter(cfg), params, ecfg, batch=batch,
                          calib_k=calib, prefix_cache=cache)
        sys_prompt = rng.integers(0, cfg.vocab_size, sys_len)
        print("flush,hit_rate,saved_prefill_tokens,resident_blocks")
        for flush in ("cold", "warm"):
            for _ in range(batch):
                prompt = np.concatenate(
                    [sys_prompt, rng.integers(0, cfg.vocab_size, user_len)])
                srv.submit(prompt, max_new=max_new)
            pc = srv.last_stats["prefix_cache"]
            stats[flush] = pc
            print(f"{flush},{pc['hit_rate']:.3f},{pc['saved_prefill_tokens']},"
                  f"{pc['resident_blocks']}")
    return stats


def main(tiny: bool = False) -> None:
    print("== modeled cold vs warm prefill (deployment dims) ==")
    ratios = run_modeled(s=512 if tiny else 4096)
    print("== live BatchServer session (tiny model, real KV restore) ==")
    session = run_session(sys_len=24 if tiny else 48,
                          user_len=8, max_new=3 if tiny else 4)
    ok_model = all(r < 0.5 for r in ratios.values())
    ok_hits = session["warm"]["hit_rate"] > 0.0
    print(f"warm<0.5x cold on all disks: {ok_model}; warm flush hit: {ok_hits}")
    if not (ok_model and ok_hits):
        raise SystemExit("prefix reuse benchmark regressed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode: small prompt sizes")
    main(tiny=ap.parse_args().tiny)
