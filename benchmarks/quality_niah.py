"""Paper Tabs. 2/3 + Fig. 9 — generation-quality proxies.

Without the real checkpoints/datasets the offline container can't run RULER,
so this benchmark measures the *mechanism* the paper's quality numbers rest
on: does each method's selection keep the KV entries the true attention
needs?  Two metrics per method × budget:

* oracle-recall of the true top-budget tokens,
* relative L2 error of the sparse attention output,

plus a Fig. 9-style needle heatmap: is the group holding a planted
high-score needle selected, across (context length × depth)?
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import Timer, correlated_kv, emit
from repro.core import baselines as B

HK, D, H = 8, 128, 32


def methods(budget_frac_mem: float):
    """Comparable in-memory metadata: rank chosen to match the budget."""
    rank = max(4, int(budget_frac_mem * HK * D))
    return [
        B.InfiniGenPolicy(HK, D, partial_ratio=budget_frac_mem),
        B.InfiniGenPolicy(HK, D, partial_ratio=budget_frac_mem, head_agg=True),
        B.LokiPolicy(HK, D, rank=rank),
        B.ShadowKVPolicy(HK, D, rank=rank),
        B.KVSwapPolicy(HK, D, group_size=4, rank=rank, reuse=False),
    ]


def fidelity_table(n_ctx=2048, budget_tokens=400, seeds=4) -> list[dict]:
    rows = []
    print("setting,policy,recall,attn_mass,out_err")
    for frac, tag in ((1 / 8, "relaxed"), (1 / 32, "tight")):
        accum: dict = {}
        for seed in range(seeds):
            rng = np.random.default_rng(seed)
            k, v = correlated_kv(rng, n_ctx, HK, D, true_rank=64)
            q = rng.standard_normal((H, D)).astype(np.float32)
            for pol in methods(frac):
                pol.reset(n_ctx)
                r = B.evaluate_policy(pol, q, k, v, budget_tokens)
                a = accum.setdefault(pol.name, {"recall": [], "mass": [], "err": []})
                a["recall"].append(r.recall)
                a["mass"].append(r.mass)
                a["err"].append(r.out_err)
        for name, a in accum.items():
            rows.append({"setting": tag, "policy": name,
                         "recall": float(np.mean(a["recall"])),
                         "mass": float(np.mean(a["mass"])),
                         "out_err": float(np.mean(a["err"]))})
            print(f"{tag},{name},{np.mean(a['recall']):.3f},"
                  f"{np.mean(a['mass']):.3f},{np.mean(a['err']):.3f}")
    return rows


def needle_heatmap(ctxs=(1024, 2048, 4096), depths=(0.1, 0.3, 0.5, 0.7, 0.9),
                   budget_tokens=400) -> np.ndarray:
    """Fig. 9 analogue: 1.0 = needle group selected (model keeps capability)."""
    grid = np.zeros((len(depths), len(ctxs)))
    rng = np.random.default_rng(0)
    for ci, n in enumerate(ctxs):
        for di, depth in enumerate(depths):
            k, v = correlated_kv(rng, n, HK, D, true_rank=64)
            # plant a needle: keys aligned with the query's per-group mean,
            # scaled to clear the background score distribution (the NIAH
            # premise: the needle IS what the true attention retrieves)
            q = rng.standard_normal((H, D)).astype(np.float32)
            qg = q.reshape(HK, H // HK, D).mean(axis=1)
            bg = np.abs(B.head_scores(q, k).sum(0)).max()
            scale = 1.5 * bg / (np.linalg.norm(qg) ** 2 / HK * (H // HK))
            pos = int(depth * (n - 8))
            for j in range(8):
                k[pos + j] = scale * qg
            pol = B.KVSwapPolicy(HK, D, group_size=4, rank=64, reuse=False)
            sel = pol.select(q, k, budget_tokens)
            hit = len(set(range(pos, pos + 8)) & set(sel.token_ids.tolist())) > 0
            grid[di, ci] = float(hit)
    print("fig9_needle_grid (rows=depth, cols=ctx):")
    print(grid)
    return grid


def main() -> str:
    with Timer() as t:
        rows = fidelity_table()
        grid = needle_heatmap()
    tight = {r["policy"]: r for r in rows if r["setting"] == "tight"}
    emit("tab2_quality", t.us,
         f"tight_out_err kvswap={tight['kvswap']['out_err']:.3f} "
         f"shadowkv={tight['shadowkv']['out_err']:.3f} "
         f"infinigen={tight['infinigen']['out_err']:.3f} "
         f"needle_hit={grid.mean():.2f}")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
