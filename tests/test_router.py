"""Multi-replica front end (src/repro/router/): pool lifecycle, policies,
router-tier shedding, fleet stats — and the two headline claims: routed
generation is bit-identical to solo unrouted sessions, and affinity
scoring (peek) is observably side-effect-free on every replica's cache.
"""

import dataclasses

import numpy as np
import pytest

from repro.cache import PrefixCache, PrefixCacheConfig
from repro.core.engine import EngineConfig
from repro.obs import Observability
from repro.router import (DRAINING, LIVE, QUIESCED, FrontEnd, LeastLoaded,
                          PrefixAffinityRouter, ReplicaPool, RoundRobin)
from repro.serving.api import ServeSession
from repro.serving.errors import RequestRejected
from repro.serving.metrics import SLOClass
from repro.serving.sampling import SamplingParams
from repro.serving.trace import mixed_tenant_trace

SLO = {"interactive": SLOClass("interactive", ttft_s=5.0, tpot_s=5.0)}


@pytest.fixture(scope="module")
def setup(tiny_cfg, tiny_params, tiny_adapter):
    rng = np.random.default_rng(11)
    calib = rng.standard_normal(
        (256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim)).astype(np.float32)
    return tiny_cfg, tiny_params, tiny_adapter, calib


def make_session(setup, *, slots=2, cache=True, obs=None, **ecfg_kw):
    cfg, params, adapter, calib = setup
    base = dict(group_size=4, n_select=6, rank=8, reuse_capacity=12,
                max_seq=128, predict_from="self")
    base.update(ecfg_kw)
    pc = PrefixCache(PrefixCacheConfig(block_tokens=8)) if cache else None
    return ServeSession(adapter, params, EngineConfig(**base), slots=slots,
                        calib_k=calib, prefix_cache=pc, obs=obs)


def fleet(setup, n=3, policy=None, cache=True, **fe_kw):
    pool = ReplicaPool()
    for i in range(n):
        pool.add(f"r{i}", make_session(setup, cache=cache))
    return FrontEnd(pool, policy or RoundRobin(), **fe_kw)


def req(prompt, max_new=3, **kw):
    return {"prompt": prompt, "max_new": max_new, **kw}


def tiny_mixed_trace(seed=7):
    return mixed_tenant_trace(seed, tenants=3, turns=3, sys_tokens=16,
                              user_tokens=8, max_new=4, slo_classes=SLO,
                              vocab_size=97)


# --------------------------------------------------------------------------
# pool lifecycle
# --------------------------------------------------------------------------

class TestPoolLifecycle:
    def test_duplicate_name_raises(self, setup):
        pool = ReplicaPool()
        pool.add("r0", make_session(setup, cache=False))
        with pytest.raises(ValueError, match="duplicate"):
            pool.add("r0", make_session(setup, cache=False))
        pool.close()

    def test_drain_stops_routing(self, setup):
        with fleet(setup, n=2, cache=False) as front:
            front.pool.drain("r0")
            assert front.pool["r0"].state == DRAINING
            assert [r.name for r in front.pool.live()] == ["r1"]
            rids = [front.submit(req(np.arange(8))) for _ in range(4)]
            assert {front.route_of(r) for r in rids} == {"r1"}

    def test_quiesce_preconditions(self, setup):
        with fleet(setup, n=2, cache=False) as front:
            with pytest.raises(ValueError, match="must be draining"):
                front.pool.quiesce("r0")
            front.submit(req(np.arange(8)))       # routed to r0 (RR)
            front.pool.drain("r0")
            with pytest.raises(ValueError, match="still has work"):
                front.pool.quiesce("r0")

    def test_drain_leaves_no_stranded_requests(self, setup):
        """Every request routed before (or during) a drain completes; the
        drained replica auto-quiesces with frozen stats once its work
        runs dry — nothing is ever stranded on a closed session."""
        with fleet(setup, n=3, cache=False) as front:
            rids = [front.submit(req(np.arange(6 + i), max_new=3))
                    for i in range(6)]           # 2 per replica (RR)
            front.pool.drain("r1")
            out = front.drain()
            assert sorted(out) == rids           # all completed, none lost
            r1 = front.pool["r1"]
            assert r1.state == QUIESCED
            assert r1.final_stats["completed_requests"] == 2
            assert front.stats()["completed_requests"] == 6
            # quiesced replicas are terminal
            with pytest.raises(ValueError, match="quiesced"):
                front.pool.drain("r1")

    def test_all_drained_sheds_typed(self, setup):
        with fleet(setup, n=2, cache=False) as front:
            front.pool.drain("r0")
            front.pool.drain("r1")
            with pytest.raises(RequestRejected) as ei:
                front.submit(req(np.arange(8)))
            assert ei.value.reason == "no_live_replicas"
            assert front.router_rejections == 1


# --------------------------------------------------------------------------
# policies
# --------------------------------------------------------------------------

class TestPolicies:
    def test_round_robin_cycles_in_pool_order(self, setup):
        with fleet(setup, n=3, cache=False) as front:
            rids = [front.submit(req(np.arange(8))) for _ in range(6)]
            assert [front.route_of(r) for r in rids] \
                == ["r0", "r1", "r2", "r0", "r1", "r2"]

    def test_least_loaded_balances(self, setup):
        with fleet(setup, n=2, policy=LeastLoaded(), cache=False) as front:
            rids = [front.submit(req(np.arange(8))) for _ in range(4)]
            # load ties break to pool order, so the pattern alternates
            assert [front.route_of(r) for r in rids] \
                == ["r0", "r1", "r0", "r1"]

    def test_affinity_deterministic_under_fixed_seed(self, setup):
        """Two identically-built fleets given the same trace route every
        request to the same replica — replica choice is a deterministic
        function of (policy state, pool order, signals)."""
        routes = []
        for _ in range(2):
            with fleet(setup, policy=PrefixAffinityRouter()) as front:
                tr = tiny_mixed_trace()
                front.replay(tr)
                routes.append([front.route_of(i)
                               for i in range(tr.n_requests)])
        assert routes[0] == routes[1]

    def test_affinity_sticks_to_warm_replica(self, setup):
        """Turn 2 of a conversation routes to whichever replica served
        (and cached) turn 1, regardless of round-robin-style churn from
        other tenants in between."""
        with fleet(setup, policy=PrefixAffinityRouter()) as front:
            tr = tiny_mixed_trace()
            front.replay(tr)
            by_tenant = {}
            for i, r in enumerate(tr.requests):
                by_tenant.setdefault(r.tenant, []).append(front.route_of(i))
            for tenant, replicas in by_tenant.items():
                assert len(set(replicas)) == 1, \
                    f"tenant {tenant} sprayed across {set(replicas)}"

    def test_affinity_hit_rate_beats_round_robin(self, setup):
        """On a trace of multi-turn tenants, routing each turn to the
        replica that cached the previous one serves more prompt tokens from
        cache than cycling the pool."""
        tr = tiny_mixed_trace()
        hit = {}
        for name, policy in (("rr", RoundRobin()),
                             ("affinity", PrefixAffinityRouter())):
            with fleet(setup, policy=policy) as front:
                st = front.replay(tr)["fleet"]
                assert st["completed_requests"] == tr.n_requests
                hit[name] = st["prefix_hit_rate"]
        assert hit["affinity"] > hit["rr"]

    def test_affinity_overload_penalty_repels(self, setup):
        """A degraded replica loses affinity units per ladder rung: even
        a fully-warm replica is out-scored by a cold idle one when it is
        shedding (the DegradationPolicy hysteresis signal)."""
        with fleet(setup, policy=PrefixAffinityRouter()) as front:
            prompt = np.arange(40)
            rid = front.submit(req(prompt, max_new=2))
            front.drain()
            warm = front.pool[front.route_of(rid)]
            pol = front.policy
            assert pol.score(warm, prompt) > max(
                pol.score(r, prompt) for r in front.pool if r is not warm)
            warm.session._degrade_level = 1       # force the ladder rung
            assert pol.score(warm, prompt) < 0.0
            assert max(front.pool, key=lambda r: pol.score(r, prompt)) \
                is not warm


# --------------------------------------------------------------------------
# shedding
# --------------------------------------------------------------------------

class TestShedding:
    def test_router_overload_sheds_typed_without_touching_sessions(
            self, setup):
        with fleet(setup, n=2, cache=False, max_queue_depth=1) as front:
            # future arrivals queue without admitting (we never step)
            for _ in range(2):
                front.submit(req(np.arange(8), arrival=100.0))
            with pytest.raises(RequestRejected) as ei:
                front.submit(req(np.arange(8), arrival=100.0))
            assert ei.value.reason == "router_overload"
            assert ei.value.max_queue_depth == 1
            assert front.router_rejections == 1
            # router-tier shed is pure bookkeeping: no session saw it
            for rep in front.pool:
                assert rep.session.rejected == 0
            assert front.stats()["router_rejections"] == 1

    def test_replica_rejection_propagates_with_name(self, setup):
        with fleet(setup, n=2, cache=False) as front:
            with pytest.raises(RequestRejected) as ei:
                front.submit(req(np.arange(200), max_new=50))
            assert ei.value.reason == "capacity"
            assert ei.value.replica == "r0"        # RR picked r0 first
            assert front.pool["r0"].shed == 1

    def test_router_metrics_labeled_per_replica(self, setup):
        obs = Observability()
        with fleet(setup, n=2, cache=False, obs=obs) as front:
            for _ in range(3):
                front.submit(req(np.arange(8)))
            snap = obs.registry.snapshot()
        assert snap['kvswap_router_requests_total{replica="r0"}'] == 2
        assert snap['kvswap_router_requests_total{replica="r1"}'] == 1


# --------------------------------------------------------------------------
# bit-identity: routed == solo unrouted
# --------------------------------------------------------------------------

class TestBitIdentity:
    def test_routed_tokens_bit_identical_to_solo_sessions(self, setup):
        """The headline determinism claim: for each replica's routed
        arrival pattern, a fresh solo ServeSession given exactly those
        submissions produces bit-identical tokens (and lifecycle
        timestamps) — routing adds nothing to the numerics."""
        tr = tiny_mixed_trace()
        with fleet(setup, policy=PrefixAffinityRouter()) as front:
            front.replay(tr)
            by_replica = {}
            for i, r in enumerate(tr.requests):
                by_replica.setdefault(front.route_of(i), []).append((i, r))
            assert len(front.results()) == tr.n_requests
            for name, routed in by_replica.items():
                solo = make_session(setup)
                with solo:
                    local = {}
                    for rid, r in routed:
                        local[rid] = solo.submit(
                            r.materialize(tr.vocab_size), r.max_new,
                            arrival=r.arrival, slo_class=r.slo_class,
                            tenant=r.tenant)
                    solo.drain()
                    routed_sess = front.pool[name].session
                    for rid, _ in routed:
                        a = front.result(rid)
                        b = solo.completed[local[rid]].output
                        np.testing.assert_array_equal(a, b)
                        fleet_req = routed_sess.completed[
                            local[rid]]  # same local rids by construction
                        assert fleet_req.finished_at \
                            == solo.completed[local[rid]].finished_at

    def test_sampled_requests_bit_identical(self, setup):
        """Stochastic sampling routes through the same per-request
        sampler machinery: a routed temperature/seed request matches the
        solo session draw for draw."""
        prompt = np.arange(12)
        with fleet(setup, n=2, cache=False) as front:
            rid = front.submit(req(prompt, max_new=6, temperature=0.8,
                                   top_k=20, seed=42))
            front.drain()
            routed = front.result(rid)
        with make_session(setup, cache=False) as solo:
            lid = solo.submit(prompt, 6, sampling=SamplingParams(
                temperature=0.8, top_k=20, seed=42))
            solo.drain()
            np.testing.assert_array_equal(routed, solo.completed[lid].output)


# --------------------------------------------------------------------------
# peek neutrality at the router tier
# --------------------------------------------------------------------------

class TestPeekNeutrality:
    def test_scoring_never_perturbs_replica_caches(self, setup):
        """Hammering the affinity score across the fleet must leave every
        replica's cache observably untouched: stats, LRU order, pins."""
        with fleet(setup, policy=PrefixAffinityRouter()) as front:
            for i in range(3):
                front.submit(req(np.arange(24) + i, max_new=2))
            front.drain()
            before = []
            for rep in front.pool:
                cache = rep.session.prefix_cache
                before.append((dataclasses.asdict(cache.stats),
                               {b: m.last_used
                                for b, m in cache.manifest.blocks.items()}))
            probe = np.arange(24)
            for _ in range(10):
                for rep in front.pool:
                    front.policy.score(rep, probe)
            for rep, (stats, lru) in zip(front.pool, before):
                cache = rep.session.prefix_cache
                assert dataclasses.asdict(cache.stats) == stats
                assert {b: m.last_used
                        for b, m in cache.manifest.blocks.items()} == lru
                assert all(m.pins == 0
                           for m in cache.manifest.blocks.values())


# --------------------------------------------------------------------------
# fleet stats
# --------------------------------------------------------------------------

class TestFleetStats:
    def test_stats_and_aggregate_consistent(self, setup):
        tr = tiny_mixed_trace()
        with fleet(setup, policy=PrefixAffinityRouter()) as front:
            out = front.replay(tr)
            st = out["fleet"]
            assert st["policy"] == "prefix_affinity"
            assert st["n_replicas"] == 3
            assert st["completed_requests"] == tr.n_requests
            assert st["routed_requests"] == tr.n_requests
            assert st["completed_requests"] == sum(
                p["session"]["completed_requests"]
                for p in st["replicas"].values())
            assert st["makespan_s"] == max(
                p["now"] for p in st["replicas"].values())
            assert 0.0 < st["prefix_hit_rate"] <= 1.0
            assert st["replicas"]["r0"]["state"] == LIVE
            # aggregation: global rids, replica attribution, fleet makespan
            recs = out["per_request"]
            assert [r["rid"] for r in recs] == list(range(tr.n_requests))
            assert all(r["replica"] in front.pool.names() for r in recs)
            assert all(r["tenant"].startswith("t") for r in recs)
            assert out["makespan_seconds"] == st["makespan_s"]

    def test_unknown_request_keys_raise(self, setup):
        with fleet(setup, n=1, cache=False) as front:
            with pytest.raises(ValueError, match="unknown request keys"):
                front.submit({"prompt": np.arange(4), "temprature": 1.0})
            with pytest.raises(ValueError, match="not both"):
                front.submit({"prompt": np.arange(4), "max_new": 2,
                              "max_tokens": 2})
