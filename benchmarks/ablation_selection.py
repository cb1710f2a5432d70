"""Paper Fig. 13b — number of selected entries MG vs throughput / accuracy.

MG↑ ⇒ recall rises with diminishing returns, throughput falls; MG=400 is the
paper's balanced default.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import LLAMA3_8B, Timer, correlated_kv, emit
from repro.core import baselines as B
from repro.core.offload import NVME, EMMC

HK, D, H = LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim, LLAMA3_8B.n_heads


def run(mgs=(100, 200, 400, 800, 1600), n_ctx=4096) -> list[dict]:
    rng = np.random.default_rng(0)
    k, v = correlated_kv(rng, n_ctx, HK, D, true_rank=64)
    q = rng.standard_normal((H, D)).astype(np.float32)
    rows = []
    print("mg,disk,tokens_per_s,recall")
    for mg in mgs:
        rec = B.evaluate_policy(
            B.KVSwapPolicy(HK, D, group_size=4, rank=32, reuse=False),
            q, k, v, mg).recall
        for disk in (NVME, EMMC):
            pol = B.KVSwapPolicy(HK, D, group_size=4, rank=32, reuse=True)
            r = B.simulate_throughput(pol, disk=disk, dims=LLAMA3_8B, n_layers=32,
                                      batch=8, n_ctx=n_ctx, budget_tokens=mg, n_steps=6)
            rows.append({"mg": mg, "disk": disk.name, "tps": r["tokens_per_s"],
                         "recall": rec})
            print(f"{mg},{disk.name},{r['tokens_per_s']:.1f},{rec:.3f}")
    return rows


def main() -> str:
    with Timer() as t:
        rows = run()
    nv = {r["mg"]: r for r in rows if r["disk"] == "nvme"}
    ok = nv[1600]["tps"] < nv[100]["tps"] and nv[1600]["recall"] >= nv[100]["recall"]
    emit("fig13b_selection", t.us,
         f"tps_mg100={nv[100]['tps']:.1f} tps_mg1600={nv[1600]['tps']:.1f} trend_ok={ok}")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
