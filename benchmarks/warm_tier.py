"""A/B the host-RAM warm tier (`repro.tiers`) against the disk-only stack.

High re-read regime: the reuse buffer is deliberately undersized relative to
the per-step working set, so most steps evict groups that the very next
steps re-select — exactly the tail the warm tier exists to absorb.  For each
disk spec (nvme / ufs / emmc) the same prompt is decoded twice, warm tier
off (``warm_budget_bytes=0``) and on, with ``kv_bits=8`` so the on-disk and
warm formats match and decoded tokens are **bit-identical** between the two
arms (asserted).

Reported per arm:

* ``read_mb``        — disk bytes actually read (the number that must drop),
* ``warm_mb``        — bytes served by the warm tier instead (disk units),
* ``warm_hit_rate``  — fraction of reuse-buffer misses the tier absorbed,
* ``step_ms``        — median modeled per-step latency (pipelined; the
                       deterministic "step wall" on the modeled platform),
* ``wall_ms``        — measured host wall per step (reported, not gated:
                       container RAM serves both memmap and tier).

Checks (full mode): tokens identical per disk; disk read bytes strictly
lower with the tier on for **every** disk; median modeled step latency
strictly lower on nvme, ufs and emmc.  Emits ``BENCH_warm_tier.json``
(``--tiny`` writes ``BENCH_warm_tier_tiny.json`` and skips the asserts
except byte reduction).

Usage::

    PYTHONPATH=src python -m benchmarks.warm_tier [--tiny] [--steps N]
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import write_bench_json  # noqa: F401  (src/ bootstrap)

from repro.core.engine import EngineConfig, KVSwapEngine
from repro.models.transformer import ModelConfig, TransformerAdapter, init_params


def build_model(tiny: bool):
    if tiny:
        cfg = ModelConfig(name="warmtier-tiny", arch_type="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          d_ff=128, vocab_size=128)
    else:
        cfg = ModelConfig(name="warmtier", arch_type="dense", n_layers=4,
                          d_model=128, n_heads=8, n_kv_heads=4, head_dim=16,
                          d_ff=256, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, TransformerAdapter(cfg), params


def run_one(adapter, params, prompt, calib, *, disk: str, warm_budget: int,
            steps: int, ecfg_kw: dict) -> tuple[np.ndarray, dict]:
    ecfg = EngineConfig(disk=disk, warm_budget_bytes=warm_budget, kv_bits=8,
                        **ecfg_kw)
    with KVSwapEngine(adapter, params, ecfg, batch=prompt.shape[0],
                      calib_k=calib) as eng:
        toks = eng.generate(prompt, steps)
        skip = min(ecfg.group_size + 2, max(1, steps - 2))
        log = eng.step_log[skip:]
        snap = eng.accountant.snapshot()
        warm = eng.warm.snapshot() if eng.warm is not None else None
        row = {
            "disk": disk,
            "warm_budget_bytes": warm_budget,
            "read_mb": snap["read_bytes"] / 1e6,
            "warm_mb": snap["warm_bytes"] / 1e6,
            "warm_hit_rate": warm["hit_rate"] if warm else 0.0,
            "step_ms": float(np.median(
                [s.pipelined_seconds for s in log])) * 1e3,
            "wall_ms": float(np.median([s.wall_seconds for s in log])) * 1e3,
            "reuse_hit_rate": eng.reuse_ratio(),
        }
    return toks, row


def main(tiny: bool = False, steps: int | None = None) -> dict:
    cfg, adapter, params = build_model(tiny)
    rng = np.random.default_rng(0)
    prompt_len = 96 if tiny else 512
    steps = steps or (10 if tiny else 24)
    batch = 2
    prompt = rng.integers(0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)
    calib = rng.standard_normal((512, cfg.n_kv_heads, cfg.head_dim)).astype(np.float32)
    ecfg_kw = dict(
        group_size=4,
        n_select=8 if tiny else 32,
        rank=16 if tiny else 32,
        # the high re-read regime: C below the per-step working set M, so
        # every step evicts groups the next steps re-select — the re-read
        # tail the warm tier absorbs (vs decode_hotpath's C >> M sizing)
        reuse_capacity=4 if tiny else 16,
        max_seq=256 if tiny else 1024,
    )
    budget = (1 << 20) if tiny else (8 << 20)
    disks = ["nvme"] if tiny else ["nvme", "ufs", "emmc"]

    rows = []
    print("disk,warm,read_mb,warm_mb,warm_hit_rate,step_ms,wall_ms")
    for disk in disks:
        arms = {}
        for wb in (0, budget):
            toks, row = run_one(adapter, params, prompt, calib, disk=disk,
                                warm_budget=wb, steps=steps, ecfg_kw=ecfg_kw)
            arms[wb] = (toks, row)
            rows.append(row)
            print(f"{disk},{bool(wb)},{row['read_mb']:.3f},{row['warm_mb']:.3f},"
                  f"{row['warm_hit_rate']:.3f},{row['step_ms']:.3f},"
                  f"{row['wall_ms']:.3f}")
        off, on = arms[0][1], arms[budget][1]
        assert np.array_equal(arms[0][0], arms[budget][0]), \
            f"warm-tier tokens diverged from the disk-only control ({disk})"
        assert on["read_mb"] < off["read_mb"], \
            f"warm tier did not reduce disk reads on {disk}"

    by_disk = {d: [r for r in rows if r["disk"] == d] for d in disks}
    summary = {}
    for d, (off, on) in by_disk.items():
        summary[d] = {
            "read_bytes_reduction": 1.0 - on["read_mb"] / max(off["read_mb"], 1e-12),
            "step_speedup": off["step_ms"] / max(on["step_ms"], 1e-12),
            "warm_hit_rate": on["warm_hit_rate"],
        }
        print(f"{d}: read_reduction={summary[d]['read_bytes_reduction']:.1%} "
              f"step_speedup={summary[d]['step_speedup']:.2f}x "
              f"warm_hit_rate={on['warm_hit_rate']:.1%}")

    out = {"model": cfg.name, "prompt_len": prompt_len, "steps": steps,
           "batch": batch, "engine": ecfg_kw, "warm_budget_bytes": budget,
           "kv_bits": 8, "results": rows, "summary": summary}
    write_bench_json("warm_tier", out, tiny=tiny)

    if not tiny:
        # the modeled median step latency is deterministic (DiskSpec +
        # ComputeSpec), so this gate is noise-free: serving re-reads from
        # host RAM must beat every modeled disk on the paper's platforms
        for d in disks:
            off, on = by_disk[d]
            assert on["step_ms"] < off["step_ms"], \
                (f"warm tier did not reduce the median modeled step on {d}: "
                 f"{on['step_ms']:.3f} >= {off['step_ms']:.3f} ms")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: nvme only, byte-reduction assert only")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    main(tiny=args.tiny, steps=args.steps)
