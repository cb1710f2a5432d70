"""Paper Fig. 13a — decoding latency breakdown of one transformer block.

Components: compute (attention+FFN+prediction), I/O (disk reads after reuse),
reuse-management overhead.  Methods ordered as in the figure: FlexGen →
InfiniGen* → InfiniGen*+reuse → ours w/o reuse → ours w/ reuse.

Second section (``run_engine_overlap``): the *real* engine, decoded through
the async prefetch pipeline (``repro.io``), reporting per-step modeled
``pipelined_seconds`` against the serial ``io_seconds + compute_seconds``
bound for both NVMe and eMMC device specs — the paper's §3.4 overlap claim,
measured on the actual runtime rather than the analytic policy simulator.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import LLAMA3_8B, Timer, emit
from repro.core import baselines as B
from repro.core.offload import NVME


def run(n_ctx=4096, budget=400, batch=8) -> dict:
    hk, d = LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim
    methods = {
        "flexgen": B.FlexGenPolicy(hk, d),
        "infinigen*": B.InfiniGenPolicy(hk, d, head_agg=True),
        "infinigen*+reu": B.InfiniGenPolicy(hk, d, head_agg=True, reuse=True),
        "ours_wo_reu": B.KVSwapPolicy(hk, d, group_size=4, rank=32, reuse=False),
        "ours_w_reu": B.KVSwapPolicy(hk, d, group_size=4, rank=32, reuse=True),
    }
    rows = {}
    print("method,io_ms,compute_ms,reuse_mgmt_ms,total_ms")
    for name, pol in methods.items():
        r = B.simulate_throughput(pol, disk=NVME, dims=LLAMA3_8B, n_layers=1,
                                  batch=batch, n_ctx=n_ctx, budget_tokens=budget,
                                  n_steps=8)
        io_ms = r["t_io"] * 1e3
        c_ms = r["t_compute"] * 1e3
        mgmt = 0.1 if "reu" in name and "wo" not in name else 0.0  # slot-table upkeep (paper: ~1 ms / 32 blocks)
        rows[name] = {"io": io_ms, "compute": c_ms, "mgmt": mgmt,
                      "total": max(io_ms, c_ms) + mgmt}
        print(f"{name},{io_ms:.2f},{c_ms:.2f},{mgmt:.2f},{rows[name]['total']:.2f}")
    return rows


def run_engine_overlap(disk: str = "nvme", *, prompt_len=192, n_new=6,
                       n_layers=4, warm_budget=0) -> dict:
    """Decode a tiny model through the async engine; report per-step overlap.

    Returns mean modeled seconds and asserts nothing — callers check that
    ``pipelined < io + compute`` (strict, since every layer has compute and
    steady-state steps miss in the reuse buffer → non-zero interior I/O).
    """
    import jax

    from repro.core.engine import EngineConfig, KVSwapEngine
    from repro.models.transformer import (ModelConfig, TransformerAdapter,
                                          init_params)

    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=n_layers,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    model = TransformerAdapter(cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (2, prompt_len)).astype(np.int32)
    calib = rng.standard_normal((256, cfg.n_kv_heads, cfg.head_dim)).astype(np.float32)
    # small M + small C ⇒ every step pulls fresh groups from disk
    ecfg = EngineConfig(group_size=4, n_select=8, rank=8, reuse_capacity=8,
                        max_seq=256, disk=disk, predict_from="prev",
                        async_io=True, warm_budget_bytes=warm_budget)
    with KVSwapEngine(model, params, ecfg, batch=2, calib_k=calib) as eng:
        eng.generate(prompt, n_new)
        rep = eng.overlap_report()
        steps = eng.step_log[1:]
        src = eng.accountant.snapshot()["served_by_source"]
    served = src["disk"]["bytes"] + src["warm"]["bytes"]
    rep["warm_hit_rate"] = src["warm"]["bytes"] / served if served else 0.0
    warm_note = (f" warm_hit={rep['warm_hit_rate']:.1%}" if warm_budget else "")
    print(f"engine[{disk}]: io={rep['io_seconds']*1e3:.3f}ms "
          f"compute={rep['compute_seconds']*1e3:.3f}ms "
          f"pipelined={rep['pipelined_seconds']*1e3:.3f}ms "
          f"saved={rep['overlap_saved_seconds']*1e3:.3f}ms "
          f"io_wait_wall={rep['io_wait_seconds']*1e3:.2f}ms{warm_note}")
    rep["strict_overlap_all_steps"] = bool(steps) and all(
        s.pipelined_seconds < s.io_seconds + s.compute_seconds for s in steps)
    return rep


def main() -> str:
    with Timer() as t:
        rows = run()
        overlap = {d: run_engine_overlap(d) for d in ("nvme", "ufs", "emmc")}
        # warm-tier arm: same undersized-C regime with a host-RAM budget;
        # the accountant's per-source breakdown supplies the hit rate
        warm = run_engine_overlap("emmc", warm_budget=4 << 20)
    ratio = rows["flexgen"]["total"] / rows["ours_w_reu"]["total"]
    ok = (rows["ours_w_reu"]["total"] < rows["ours_wo_reu"]["total"]
          < rows["infinigen*"]["total"] < rows["flexgen"]["total"])
    pipelined_ok = all(r["strict_overlap_all_steps"] for r in overlap.values())
    emit("fig13a_latency", t.us,
         f"flexgen/ours={ratio:.1f}x ordering_ok={ok} "
         f"async_overlap_ok={pipelined_ok} "
         f"warm_hit_emmc={warm['warm_hit_rate']:.1%}")
    return "ok" if pipelined_ok else "overlap-violation"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    sys.exit(0 if main() == "ok" else 1)
