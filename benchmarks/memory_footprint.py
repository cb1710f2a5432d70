"""Paper Fig. 1 + Fig. 3a — KV cache growth and management-memory comparison.

Fig. 1: full KV cache bytes vs context length × batch (Qwen3-4B-like dims).
Fig. 3a: in-memory management footprint of each method vs full-cache, for
LLaMA3-8B at batch 8 — KVSwap's compressed-K + buffers vs InfiniGen's
partial-K and ShadowKV's low-rank-K+landmarks.

Warm-tier audit: fills a real `repro.tiers.WarmTier` past its budget and
checks the accounting invariant the `warm_budget_bytes` knob promises —
resident slab bytes + per-entry index overhead never exceed the budget
(what `KVSwapEngine.metadata_bytes()` reports as `warm_tier` +
`warm_tier_index`).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import LLAMA3_8B, Timer, emit
from repro.tiers import WarmTier
from repro.utils import GiB, MiB, fmt_bytes

FP16 = 2


def full_kv_bytes(n_layers, hk, d, batch, ctx, dtype_bytes=FP16):
    return n_layers * batch * ctx * 2 * hk * d * dtype_bytes


def fig1_kv_growth():
    # Qwen3-4B: 36 layers, 8 kv heads, d=128
    print("batch,context,kv_gib")
    rows = []
    for b in (1, 4, 8, 12):
        for ctx in (4096, 8192, 16384, 32768):
            kv = full_kv_bytes(36, 8, 128, b, ctx)
            rows.append((b, ctx, kv / GiB))
            print(f"{b},{ctx},{kv / GiB:.1f}")
    return rows


def fig3a_management_memory(batch=8):
    n_layers, hk, d = 32, LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim
    feat = hk * d
    print("context,full_kv,infinigen,shadowkv,kvswap")
    rows = []
    for ctx in (4096, 8192, 16384, 32768):
        full = full_kv_bytes(n_layers, hk, d, batch, ctx)
        # InfiniGen: partial K (ratio 0.5) resident + speculation buffers
        infinigen = n_layers * batch * ctx * feat * FP16 * 0.5
        # ShadowKV: low-rank K (rank 160) + landmarks + staging (resident V loads)
        shadowkv = n_layers * batch * ctx * 160 * FP16 * 1.25
        # KVSwap: σ=32 compressed K + reuse (C=128 groups of 4) + rolling
        kvswap = (n_layers * batch * ctx * (feat // 32) * FP16
                  + n_layers * batch * 128 * 4 * 2 * feat * FP16
                  + n_layers * batch * 4 * 2 * feat * FP16)
        rows.append((ctx, full, infinigen, shadowkv, kvswap))
        print(f"{ctx},{fmt_bytes(full)},{fmt_bytes(infinigen)},"
              f"{fmt_bytes(shadowkv)},{fmt_bytes(kvswap)}")
    return rows


def warm_tier_budget_audit(budget=16 * MiB, g=4):
    """Overfill a real warm tier and audit resident bytes against the knob."""
    hk, d = LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim
    tier = WarmTier(budget_bytes=budget)
    rng = np.random.default_rng(0)
    group = rng.standard_normal((g, 2, hk, d)).astype(np.float32)
    per_entry = g * 2 * hk * d + 4  # int8 payload + scale
    n = budget // per_entry + 64    # deliberately past the budget
    for i in range(n):
        tier.admit(i % 32, i % 8, i, group)
    snap = tier.snapshot()
    resident = tier.nbytes + tier.index_nbytes
    print(f"warm_budget={fmt_bytes(budget)} slab={fmt_bytes(tier.nbytes)} "
          f"index={fmt_bytes(tier.index_nbytes)} resident={fmt_bytes(resident)} "
          f"entries={snap['entries']} evicted={snap['evicted']}")
    assert resident <= budget, "warm tier overran its budget"
    assert snap["evicted"] > 0, "audit never reached the eviction regime"
    return resident


def main() -> str:
    with Timer() as t:
        fig1_kv_growth()
        rows = fig3a_management_memory()
        warm_resident = warm_tier_budget_audit()
    ctx32k = rows[-1]
    reduction = ctx32k[1] / ctx32k[4]
    emit("fig1_fig3a_memory", t.us,
         f"kv32k_b8={fmt_bytes(ctx32k[1])} kvswap_reduction={reduction:.0f}x "
         f"warm_tier_resident={fmt_bytes(warm_resident)}")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
