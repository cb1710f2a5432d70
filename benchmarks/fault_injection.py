"""Storage fault injection: graceful degradation across the tier stack.

Drives a real :class:`~repro.serving.api.ServeSession` (tiny model, greedy
sampling) through a fixed request trace under seeded
:class:`~repro.faults.plan.FaultPlan` campaigns, sweeping fault rate ×
disk class, and asserts the robustness contract (docs/robustness.md):

* **transient** faults (device read errors + short reads, burst below the
  retry budget) are absorbed by retry-with-backoff: every request's token
  stream is **bit-identical** to the fault-free run, no request fails, and
  zero prefetch worker threads die;
* **GC spikes** (emmc/ufs flash stalls) charge modeled time but change no
  bytes: tokens stay bit-identical while ``modeled_seconds`` and the
  accountant's ``stall_seconds`` lane grow;
* **persistent** faults (grown bad extents) are *bounded*: the session
  finishes the whole trace with the affected requests in the FAILED
  terminal state and every other request completed — never an uncaught
  exception, never a crashed session.

Usage::

    PYTHONPATH=src python -m benchmarks.fault_injection [--tiny]
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import write_bench_json

DISK_SWEEP = ("nvme", "ufs", "emmc")


def build_session(*, disk: str, faults=None, async_io: bool = True,
                  slots: int = 2, max_seq: int = 96):
    import jax

    from repro.core.engine import EngineConfig
    from repro.models.transformer import (ModelConfig, TransformerAdapter,
                                          init_params)
    from repro.serving.api import ServeSession

    cfg = ModelConfig(name="bench", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=211)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((128, cfg.n_kv_heads, cfg.head_dim))
    ecfg = EngineConfig(group_size=4, n_select=6, rank=8, reuse_capacity=8,
                        max_seq=max_seq, predict_from="self", disk=disk,
                        async_io=async_io)
    return ServeSession(TransformerAdapter(cfg), params, ecfg, slots=slots,
                        calib_k=calib, faults=faults)


def run_trace(session, *, n_requests: int, prompt_len: int,
              max_new: int) -> dict:
    """Submit a fixed trace, drain, and flatten the outcome."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 211, prompt_len) for _ in range(n_requests)]
    rids = [session.submit(p, max_new=max_new, arrival=0.05 * i)
            for i, p in enumerate(prompts)]
    session.drain()
    stats = session.stats()
    eng = session.engine
    tokens = {rid: (session.completed[rid].output.tolist()
                    if rid in session.completed else None)
              for rid in rids}
    out = {
        "tokens": tokens,
        "completed": len(session.completed),
        "failed": len(session.failed),
        "failed_errors": {rid: r.error for rid, r in session.failed.items()},
        "modeled_seconds": stats["modeled_seconds"],
        "io_retries": stats["io_retries"],
        "fetch_failures": stats["fetch_failures"],
        "recovered_rows": stats["recovered_rows"],
        "stall_seconds": stats["stall_seconds"],
        "worker_deaths": (eng.prefetcher.deaths
                          if eng.prefetcher is not None else 0),
        "workers_alive": (eng.prefetcher.alive_threads()
                          if eng.prefetcher is not None else 0),
        "workers_total": (len(eng.prefetcher._threads)
                          if eng.prefetcher is not None else 0),
    }
    session.close()
    return out


def run_campaign(disk: str, *, tiny: bool) -> dict:
    from repro.faults import FaultPlan, FaultSpec

    n_requests = 3 if tiny else 6
    prompt_len = 24 if tiny else 40
    max_new = 4 if tiny else 8
    kw = dict(n_requests=n_requests, prompt_len=prompt_len, max_new=max_new)

    scenarios = {
        "baseline": None,
        "transient": FaultSpec(seed=3, read_error_rate=0.25,
                               torn_read_rate=0.15, error_burst=1),
        "spikes": FaultSpec(seed=5, spike_rate=0.5, spike_seconds=0.004),
        "persistent": FaultSpec(seed=11, bad_extent_rate=0.35),
    }
    out = {}
    for name, spec in scenarios.items():
        plan = None if spec is None else FaultPlan(spec)
        session = build_session(disk=disk, faults=plan)
        res = run_trace(session, **kw)
        if plan is not None:
            res["injected"] = plan.snapshot()
        out[name] = res

    base = out["baseline"]
    assert base["failed"] == 0, f"{disk}: fault-free run failed requests"

    # -- transient: retries make faults invisible except in the counters --
    tr = out["transient"]
    assert tr["tokens"] == base["tokens"], \
        f"{disk}: tokens diverged under transient faults"
    assert tr["failed"] == 0, f"{disk}: transient faults failed a request"
    assert tr["io_retries"] > 0, f"{disk}: transient campaign injected nothing"
    assert tr["worker_deaths"] == 0 and \
        tr["workers_alive"] == tr["workers_total"], \
        f"{disk}: prefetch workers died under transient faults"

    # -- spikes: time-only faults; fire only on flash disk classes --------
    sp = out["spikes"]
    assert sp["tokens"] == base["tokens"], \
        f"{disk}: tokens diverged under GC spikes"
    if disk in ("emmc", "ufs"):
        assert sp["stall_seconds"] > 0, f"{disk}: no spike ever charged"
        assert sp["modeled_seconds"] > base["modeled_seconds"], \
            f"{disk}: spikes did not slow the modeled clock"
    else:
        assert sp["stall_seconds"] == 0, f"{disk}: spike fired on nvme"

    # -- persistent: bounded degradation, never a crash -------------------
    pe = out["persistent"]
    assert pe["completed"] + pe["failed"] == n_requests, \
        f"{disk}: persistent campaign lost a request"
    for rid, toks in pe["tokens"].items():
        if toks is not None:
            assert toks == base["tokens"][rid], \
                f"{disk}: a *surviving* request's tokens diverged"
    assert pe["worker_deaths"] == 0, \
        f"{disk}: prefetch workers died under persistent faults"
    return out


def main(tiny: bool = False) -> None:
    payload = {}
    print("disk,scenario,completed,failed,retries,fetch_failures,"
          "recovered_rows,stall_ms,modeled_s")
    any_failed = 0
    for disk in DISK_SWEEP:
        payload[disk] = run_campaign(disk, tiny=tiny)
        for name, res in payload[disk].items():
            print(f"{disk},{name},{res['completed']},{res['failed']},"
                  f"{res['io_retries']},{res['fetch_failures']},"
                  f"{res['recovered_rows']},{res['stall_seconds'] * 1e3:.2f},"
                  f"{res['modeled_seconds']:.4f}")
            any_failed += res["failed"]
    # the persistent campaign must actually exercise the failure path on at
    # least one disk, or the sweep proves nothing
    assert any_failed > 0, "no persistent fault ever escalated; raise the rate"
    summary = {
        "disks": list(DISK_SWEEP),
        "transient_bit_identical": True,   # asserted per disk above
        "persistent_failed_requests": any_failed,
        "results": payload,
    }
    write_bench_json("fault_injection", summary, tiny=tiny)
    print("fault injection sweep: all robustness assertions held")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode: fewer/shorter requests")
    main(tiny=ap.parse_args().tiny)
