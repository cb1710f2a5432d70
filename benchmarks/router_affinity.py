"""Multi-replica affinity routing benchmark: KV locality as a fleet asset.

A mixed-tenant chat trace (interleaved per-tenant conversations with
growing shared prefixes, Poisson-ish start offsets and think-time gaps —
:func:`repro.serving.trace.mixed_tenant_trace`) is routed across N=3
independent replicas (each its own ServeSession + int8 PrefixCache) by
two policies:

* ``round_robin`` — the locality-blind baseline: a tenant's turns spray
  across replicas, so each replica holds only a fragment of the
  conversation's block chain and most prefills run cold;
* ``prefix_affinity`` — scores replicas by longest cached prefix (the
  side-effect-free ``PrefixCache.peek`` over the same content-addressed
  chain) blended with load, keeping every tenant's turns on the replica
  that already holds their KV.

Sweep: disk ∈ {nvme, ufs} × policy, near fleet saturation (arrival
pacing calibrated from a solo ufs service probe), modeled Orin-Nano
compute, int8 disk tier + int8 prefix slabs — the slo_trace platform.

Asserted invariants (the run fails otherwise):

* every disk: affinity **beats** round-robin on the fleet warm-prefill
  hit rate (cached prompt tokens / prompt tokens) — the locality claim;
* every disk: affinity **beats** round-robin on goodput-under-SLO —
  locality translates into latency headroom under load, not just fewer
  reads;
* routed generation is **bit-identical** to solo unrouted sessions: for
  each replica's routed arrival pattern, a fresh solo session given
  exactly those submissions reproduces every token stream;
* both policies complete every trace request (no shedding is configured,
  so a loss would be a scheduler bug).

    PYTHONPATH=src python -m benchmarks.router_affinity [--tiny]
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from benchmarks.common import write_bench_json  # noqa: F401  (src/ bootstrap)

EPS = 1e-9
N_REPLICAS = 3


def build_model():
    import jax

    from repro.models.transformer import ModelConfig, init_params

    # the slo_trace platform: small enough for CPU prefill in seconds, big
    # enough that modeled Orin-Nano prefill compute dominates a same-length
    # int8 restore read — the regime where prefix locality pays
    cfg = ModelConfig(name="router-bench", arch_type="dense", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=1, head_dim=16,
                      d_ff=1024, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def base_engine_cfg(max_seq: int):
    from repro.core.engine import EngineConfig

    return EngineConfig(group_size=4, n_select=20, rank=16,
                        reuse_capacity=12, max_seq=max_seq, kv_bits=8,
                        predict_from="self", compute="jetson-orin-nano")


def make_session(cfg, params, calib, ecfg, *, slots, prefix_cache=None):
    from repro.models.transformer import TransformerAdapter
    from repro.serving.api import ServeSession

    return ServeSession(TransformerAdapter(cfg), params, ecfg, slots=slots,
                        calib_k=calib, prefix_cache=prefix_cache)


def make_fleet(cfg, params, calib, ecfg, policy, *, slots):
    from repro.cache import PrefixCache, PrefixCacheConfig
    from repro.router import FrontEnd, ReplicaPool

    pool = ReplicaPool()
    for i in range(N_REPLICAS):
        pc = PrefixCache(PrefixCacheConfig(block_tokens=8, kv_bits=8))
        pool.add(f"r{i}",
                 make_session(cfg, params, calib, ecfg, slots=slots,
                              prefix_cache=pc))
    return FrontEnd(pool, policy)


def probe_service(cfg, params, calib, ecfg, *, prompt_tokens, max_new,
                  rng) -> dict:
    """Solo-request cold service profile on an idle ufs session — the
    time scale the SLO thresholds and arrival pacing derive from."""
    dcfg = dataclasses.replace(ecfg, disk="ufs")
    with make_session(cfg, params, calib, dcfg, slots=1) as sess:
        sess.submit(rng.integers(0, cfg.vocab_size, prompt_tokens), max_new)
        sess.drain()
        rec = sess.per_request()[0]
        return {"ttft_s": rec["ttft_seconds"], "tpot_s": rec["tpot_seconds"],
                "service_s": rec["e2e_seconds"]}


def run_fleet(cfg, params, calib, ecfg, policy, trace, *, slots):
    """One cell: fresh fleet, route the trace as-it-arrives, return the
    aggregate plus the per-request routing table (for bit-identity)."""
    front = make_fleet(cfg, params, calib, ecfg, policy, slots=slots)
    try:
        out = front.replay(trace)
        routes = [front.route_of(i) for i in range(trace.n_requests)]
        tokens = {i: np.asarray(front.result(i)).tolist()
                  for i in range(trace.n_requests)}
        return out, routes, tokens
    finally:
        front.close()


def verify_bit_identity(cfg, params, calib, ecfg, trace, routes, tokens,
                        *, slots) -> list[str]:
    """Replay each replica's routed arrival pattern through a fresh solo
    session; every token stream must match the routed run exactly."""
    from repro.cache import PrefixCache, PrefixCacheConfig

    failures = []
    by_replica: dict[str, list[int]] = {}
    for i, name in enumerate(routes):
        by_replica.setdefault(name, []).append(i)
    for name, rids in by_replica.items():
        with PrefixCache(PrefixCacheConfig(block_tokens=8, kv_bits=8)) as pc:
            with make_session(cfg, params, calib, ecfg, slots=slots,
                              prefix_cache=pc) as solo:
                local = {}
                for i in rids:
                    r = trace.requests[i]
                    local[i] = solo.submit(
                        r.materialize(trace.vocab_size), r.max_new,
                        arrival=r.arrival, slo_class=r.slo_class,
                        tenant=r.tenant)
                solo.drain()
                for i in rids:
                    got = np.asarray(solo.completed[local[i]].output).tolist()
                    if got != tokens[i]:
                        failures.append(
                            f"request {i} on {name}: routed tokens diverge "
                            f"from solo session")
    return failures


def main(tiny: bool = False) -> None:
    from repro.router import PrefixAffinityRouter, RoundRobin
    from repro.serving.metrics import SLOClass
    from repro.serving.trace import mixed_tenant_trace

    cfg, params = build_model()
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((256, cfg.n_kv_heads, cfg.head_dim)
                                ).astype(np.float32)
    slots = 2
    tenants, turns = (4, 3) if tiny else (6, 4)
    sys_tokens, user_tokens, max_new = 96, 16, 10
    max_seq = 320
    ecfg = base_engine_cfg(max_seq)

    # -- calibrate: SLO thresholds + arrival pacing off a ufs solo probe --
    final_prompt = sys_tokens + turns * user_tokens
    probe = probe_service(cfg, params, calib, ecfg, rng=rng,
                          prompt_tokens=final_prompt, max_new=max_new)
    slo_classes = {"interactive": SLOClass(
        "interactive", ttft_s=1.5 * probe["ttft_s"],
        tpot_s=2.0 * probe["tpot_s"])}
    # pace the fleet near saturation: `tenants` arrivals per turn gap vs
    # N_REPLICAS * slots cold service lanes, ~90 % utilization — busy
    # enough that locality decides who meets the SLO, not so overloaded
    # that every policy drowns
    turn_gap = tenants * probe["service_s"] / (N_REPLICAS * slots) / 0.9
    trace = mixed_tenant_trace(
        17, tenants=tenants, turns=turns, sys_tokens=sys_tokens,
        user_tokens=user_tokens, max_new=max_new, turn_gap_s=turn_gap,
        start_spread_s=turn_gap / tenants, slo_classes=slo_classes,
        vocab_size=cfg.vocab_size)

    disks = ("nvme",) if tiny else ("nvme", "ufs")
    policies = {"round_robin": RoundRobin, "prefix_affinity": PrefixAffinityRouter}
    out = {
        "model": dataclasses.asdict(cfg),
        "engine": {"base": dataclasses.asdict(ecfg), "slots": slots,
                   "n_replicas": N_REPLICAS},
        "slo_classes": {n: c.to_dict() for n, c in slo_classes.items()},
        "probe_ufs": probe,
        "trace": {"workload": trace.workload, "seed": trace.seed,
                  "tenants": tenants, "turns": turns,
                  "n_requests": trace.n_requests, "turn_gap_s": turn_gap},
        "disks": {},
    }
    failures: list[str] = []
    print("disk,policy,prefix_hit_rate,ttft_p95_ms,slo_attainment,"
          "goodput_under_slo_tok_s,routed_spread")
    for disk in disks:
        dcfg = dataclasses.replace(ecfg, disk=disk)
        cells = out["disks"][disk] = {}
        for pname, pcls in policies.items():
            m, routes, tokens = run_fleet(cfg, params, calib, dcfg, pcls(),
                                          trace, slots=slots)
            fleet = m.pop("fleet")
            del m["per_request"]
            spread = {n: p["routed"] for n, p in fleet["replicas"].items()}
            cells[pname] = {
                **m,
                "prefix_hit_rate": fleet["prefix_hit_rate"],
                "cached_prompt_tokens": fleet["cached_prompt_tokens"],
                "completed_requests": fleet["completed_requests"],
                "routed_spread": spread,
            }
            print(f"{disk},{pname},{fleet['prefix_hit_rate']:.3f},"
                  f"{m['ttft']['p95'] * 1e3:.3f},{m['slo_attainment']:.2f},"
                  f"{m['goodput_under_slo_tokens_per_s']:.1f},{spread}")
            if fleet["completed_requests"] != trace.n_requests:
                failures.append(
                    f"{disk}/{pname}: completed "
                    f"{fleet['completed_requests']} of {trace.n_requests}")
            if pname == "prefix_affinity":
                failures += verify_bit_identity(
                    cfg, params, calib, dcfg, trace, routes, tokens,
                    slots=slots)
        rr, aff = cells["round_robin"], cells["prefix_affinity"]
        if aff["prefix_hit_rate"] <= rr["prefix_hit_rate"] + EPS:
            failures.append(
                f"{disk}: affinity warm-prefill hit rate "
                f"{aff['prefix_hit_rate']:.3f} does not beat round-robin "
                f"{rr['prefix_hit_rate']:.3f}")
        if aff["goodput_under_slo_tokens_per_s"] <= \
                rr["goodput_under_slo_tokens_per_s"] + EPS:
            failures.append(
                f"{disk}: affinity goodput-under-SLO "
                f"{aff['goodput_under_slo_tokens_per_s']:.2f} does not beat "
                f"round-robin "
                f"{rr['goodput_under_slo_tokens_per_s']:.2f}")

    out["invariants_ok"] = not failures
    write_bench_json("router_affinity", out, tiny=tiny)
    if failures:
        raise SystemExit("router affinity invariants failed:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode: nvme only, smaller trace")
    main(tiny=ap.parse_args().tiny)
