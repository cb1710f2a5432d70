"""Paper Fig. 12 — group size G vs throughput / accuracy / I/O utilization.

Reproduces the shape of the trade-off: G↑ ⇒ throughput and effective-BW
utilization rise (block-sized reads), oracle-recall drifts down (coarser
selection).  Reuse is DISABLED here, as in the paper's ablation.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import LLAMA3_8B, Timer, correlated_kv, emit
from repro.core import baselines as B
from repro.core.offload import DISKS

HK, D, H = LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim, LLAMA3_8B.n_heads


def run(gs=(1, 2, 4, 8, 12, 16), budget=400, n_ctx=4096) -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)
    k, v = correlated_kv(rng, n_ctx, HK, D, true_rank=64)
    q = rng.standard_normal((H, D)).astype(np.float32)
    print("group_size,disk,tokens_per_s,recall,io_util")
    for g in gs:
        pol_q = B.KVSwapPolicy(HK, D, group_size=g, rank=32, reuse=False)
        rec = B.evaluate_policy(pol_q, q, k, v, budget).recall
        for disk_name, disk in DISKS.items():
            pol = B.KVSwapPolicy(HK, D, group_size=g, rank=32, reuse=False)
            r = B.simulate_throughput(pol, disk=disk, dims=LLAMA3_8B, n_layers=32,
                                      batch=8, n_ctx=n_ctx, budget_tokens=budget,
                                      n_steps=6)
            eff_bw = r["io_bytes_per_step"] / max(r["t_io"] / (32 * 8), 1e-12)
            util = min(1.0, eff_bw / disk.peak_bw)
            rows.append({"g": g, "disk": disk_name, "tps": r["tokens_per_s"],
                         "recall": rec, "util": util})
            print(f"{g},{disk_name},{r['tokens_per_s']:.1f},{rec:.3f},{util:.2f}")
    return rows


def main() -> str:
    with Timer() as t:
        rows = run()
    nvme = [r for r in rows if r["disk"] == "nvme"]
    tps_by_g = {r["g"]: r["tps"] for r in nvme}
    rec_by_g = {r["g"]: r["recall"] for r in nvme}
    # paper: throughput rises with G while accuracy degrades gradually
    ok = tps_by_g[8] > tps_by_g[1] and rec_by_g[16] <= rec_by_g[1] + 0.05
    emit("fig12_group_size", t.us,
         f"tps_g1={tps_by_g[1]:.1f} tps_g8={tps_by_g[8]:.1f} trend_ok={ok}")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
