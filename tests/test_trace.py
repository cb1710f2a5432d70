"""Trace format, replay determinism, lifecycle timestamps, SLO metrics."""

import dataclasses
import json

import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.serving import trace as trace_mod
from repro.serving.api import Request, ServeSession
from repro.serving.metrics import (SLOClass, aggregate_requests,
                                   per_request_breakdown, request_record)
from repro.serving.trace import (GENERATORS, Trace, TraceRequest,
                                 burst_trace, chat_trace, replay)
from repro.utils.stats import percentile, percentiles

SLO = {"interactive": SLOClass("interactive", ttft_s=0.5, tpot_s=0.1),
       "batch": SLOClass("batch", ttft_s=2.0, tpot_s=0.5),
       "bulk": SLOClass("bulk", ttft_s=1.5, tpot_s=0.3)}


def make_cfg(**kw):
    base = dict(group_size=4, n_select=6, rank=8, reuse_capacity=12,
                max_seq=128, predict_from="self")
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def setup(tiny_cfg, tiny_params, tiny_adapter):
    rng = np.random.default_rng(5)
    calib = rng.standard_normal(
        (256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim)).astype(np.float32)
    return tiny_cfg, tiny_params, tiny_adapter, calib


def session(setup, ecfg=None, slots=2, **kw):
    cfg, params, adapter, calib = setup
    return ServeSession(adapter, params, ecfg or make_cfg(), slots=slots,
                        calib_k=calib, **kw)


def tiny_trace(vocab=97):
    return burst_trace(3, bursts=2, burst_size=3, quiet_s=0.1,
                       within_s=0.01, prompt_tokens=(16, 24),
                       max_new_choices=(3, 5), slo_classes=SLO,
                       vocab_size=vocab)


class TestTraceSchema:
    @pytest.mark.parametrize("workload", sorted(GENERATORS))
    def test_roundtrip_generate_dump_load(self, tmp_path, workload):
        tr = GENERATORS[workload](7, slo_classes=SLO)
        path = tmp_path / f"{workload}.jsonl"
        tr.save(path)
        tr2 = Trace.load(path)
        assert tr2 == tr
        for a, b in zip(tr.prompts(), tr2.prompts()):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("workload", sorted(GENERATORS))
    def test_generator_is_seed_deterministic(self, workload):
        gen = GENERATORS[workload]
        assert gen(7, slo_classes=SLO) == gen(7, slo_classes=SLO)
        assert gen(7, slo_classes=SLO) != gen(8, slo_classes=SLO)

    @pytest.mark.parametrize("workload", sorted(GENERATORS))
    def test_generator_well_formed(self, workload):
        tr = GENERATORS[workload](7, slo_classes=SLO, vocab_size=97)
        assert [r.rid for r in tr.requests] == list(range(tr.n_requests))
        arrivals = [r.arrival for r in tr.requests]
        assert arrivals == sorted(arrivals)
        assert all(r.slo_class in SLO for r in tr.requests)
        for p in tr.prompts():
            assert p.dtype == np.int64 and len(p) > 0
            assert 0 <= p.min() and p.max() < 97

    def test_explicit_tokens_roundtrip(self, tmp_path):
        tr = Trace(workload="hand", seed=0, vocab_size=10, slo_classes={},
                   requests=[TraceRequest(rid=0, arrival=0.0, max_new=2,
                                          tokens=(1, 2, 3))])
        path = tmp_path / "hand.jsonl"
        tr.save(path)
        tr2 = Trace.load(path)
        np.testing.assert_array_equal(tr2.requests[0].materialize(10),
                                      [1, 2, 3])

    def test_load_rejects_foreign_and_future(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text(json.dumps({"format": "something-else"}) + "\n")
        with pytest.raises(ValueError, match="not a kvswap-trace"):
            Trace.load(p)
        p.write_text(json.dumps({"format": "kvswap-trace", "version": 99,
                                 "workload": "x", "seed": 0,
                                 "vocab_size": 8}) + "\n")
        with pytest.raises(ValueError, match="newer"):
            Trace.load(p)

    def test_load_accepts_version_1(self, tmp_path):
        """v1 files (pre-tenant) stay readable: absent ``tenant`` reads as
        the empty label and the declared version is preserved."""
        p = tmp_path / "v1.jsonl"
        p.write_text(
            json.dumps({"format": "kvswap-trace", "version": 1,
                        "workload": "chat", "seed": 7, "vocab_size": 97,
                        "slo_classes": {}}) + "\n"
            + json.dumps({"rid": 0, "arrival": 0.0, "max_new": 2,
                          "slo_class": "interactive",
                          "segments": [[7000001, 8]]}) + "\n")
        tr = Trace.load(p)
        assert tr.version == 1
        assert tr.requests[0].tenant == ""

    def test_mixed_tenant_labels_and_roundtrip(self, tmp_path):
        tr = trace_mod.mixed_tenant_trace(7, tenants=3, turns=2,
                                          slo_classes=SLO)
        assert {r.tenant for r in tr.requests} == {"t0", "t1", "t2"}
        # per-tenant turns extend each other token-for-token (the
        # prefix-affinity property the router leans on)
        by_tenant = {}
        for r in tr.requests:
            by_tenant.setdefault(r.tenant, []).append(r)
        for turns in by_tenant.values():
            turns.sort(key=lambda r: len(r.segments))
            for prev, cur in zip(turns, turns[1:]):
                assert cur.segments[:len(prev.segments)] == prev.segments
        tr.save(tmp_path / "mt.jsonl")
        tr2 = Trace.load(tmp_path / "mt.jsonl")
        assert tr2 == tr and tr2.version == trace_mod.TRACE_VERSION == 2

    def test_chat_turns_share_token_prefixes(self):
        """The prefix-reuse-heavy property is structural: turn t's prompt
        extends turn t-1's token-for-token."""
        tr = chat_trace(7, conversations=2, turns=3, slo_classes=SLO)
        by_head = {}
        for r in tr.requests:
            by_head.setdefault(r.segments[0], []).append(r)
        for turns in by_head.values():
            turns.sort(key=lambda r: len(r.segments))
            assert len(turns) == 3
            for prev, cur in zip(turns, turns[1:]):
                assert cur.segments[:len(prev.segments)] == prev.segments
                a = prev.materialize(tr.vocab_size)
                b = cur.materialize(tr.vocab_size)
                np.testing.assert_array_equal(a, b[:len(a)])


class TestReplay:
    def test_replay_metrics_json_byte_identical(self, setup):
        """Same trace + same config => byte-identical metrics JSON (the
        harness's determinism contract, sync engine)."""
        tr = tiny_trace()
        blobs = []
        for _ in range(2):
            with session(setup) as sess:
                m = replay(tr, sess)
            blobs.append(json.dumps(m, sort_keys=True))
        assert blobs[0] == blobs[1]

    def test_replay_via_file_matches_in_memory(self, setup, tmp_path):
        """generate -> dump -> load -> replay equals replaying the
        in-memory trace (schema round-trip covers the replay path)."""
        tr = tiny_trace()
        tr.save(tmp_path / "t.jsonl")
        with session(setup) as sess:
            a = replay(tr, sess)
        with session(setup) as sess:
            b = replay(Trace.load(tmp_path / "t.jsonl"), sess)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)

    def test_lifecycle_timestamps_ordered(self, setup):
        tr = tiny_trace()
        with session(setup) as sess:
            m = replay(tr, sess)
            reqs = sess.completed
        assert m["requests"] == tr.n_requests
        for rec in m["per_request"]:
            assert rec["arrival"] <= rec["admitted_at"] \
                <= rec["first_token_at"] <= rec["finished_at"]
            assert rec["ttft_seconds"] > 0
            assert rec["tpot_seconds"] >= 0
            r = reqs[rec["rid"]]
            assert rec["tokens"] == len(r.output)
            assert rec["slo_class"] == r.slo_class

    def test_single_token_request_first_equals_finish(self, setup):
        with session(setup, slots=1) as sess:
            rid = sess.submit(np.arange(8), max_new=1)
            sess.drain()
            req = sess.completed[rid]
        assert req.first_token_at == req.finished_at
        assert request_record(req)["tpot_seconds"] == 0.0

    def test_replay_requires_fresh_session(self, setup):
        tr = tiny_trace()
        with session(setup) as sess:
            sess.submit(np.arange(8), max_new=1)
            sess.drain()
            with pytest.raises(ValueError, match="fresh"):
                replay(tr, sess)

    def test_goodput_under_slo_bounded(self, setup):
        with session(setup) as sess:
            m = replay(tiny_trace(), sess)
        assert 0.0 <= m["goodput_under_slo_tokens_per_s"] \
            <= m["goodput_tokens_per_s"] + 1e-12

    def test_warm_prefix_replay_completes_with_bounded_attainment(self,
                                                                  setup):
        """The chat workload replayed through the full stack (int8 disk
        tier, warm tier, prefix cache) completes every request, restores
        cached tokens, and keeps every class's attainment in [0, 1]."""
        from repro.cache import PrefixCache, PrefixCacheConfig

        tr = chat_trace(7, conversations=2, turns=3, sys_tokens=24,
                        user_tokens=8, max_new=4, turn_gap_s=1.0,
                        slo_classes=SLO, vocab_size=97)
        ecfg = make_cfg(kv_bits=8, warm_budget_bytes=1 << 20)
        with PrefixCache(PrefixCacheConfig(block_tokens=8, kv_bits=8)) as pc:
            with session(setup, ecfg, prefix_cache=pc) as sess:
                m = replay(tr, sess)
        assert m["requests"] == tr.n_requests
        assert m["cached_prompt_tokens"] > 0
        assert m["slo"] and all(0.0 <= b["attainment"] <= 1.0
                                for b in m["slo"].values())

    def test_chat_replay_hits_prefix_cache(self, setup):
        """Replaying the chat workload through a prefix-cached session
        restores later turns (the workload shape does what it claims)."""
        from repro.cache import PrefixCache, PrefixCacheConfig

        tr = chat_trace(7, conversations=1, turns=3, sys_tokens=24,
                        user_tokens=8, max_new=4, turn_gap_s=1.0,
                        slo_classes=SLO, vocab_size=97)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as pc:
            with session(setup, slots=1, prefix_cache=pc) as sess:
                m = replay(tr, sess)
        assert m["cached_prompt_tokens"] > 0


class TestPerRequestBreakdown:
    def _req(self, rid, arrival, admitted, first, finished, n_out, *,
             slo="interactive", cached=0):
        r = Request(rid=rid, prompt=np.arange(10), max_new=n_out,
                    arrival=arrival, slo_class=slo)
        r.admitted_at, r.first_token_at, r.finished_at = \
            admitted, first, finished
        r.output = np.zeros(n_out, np.int64)
        r.cached_tokens = cached
        return r

    def test_record_fields(self):
        rec = request_record(
            self._req(3, 1.0, 1.5, 1.5, 3.5, 5, cached=8))
        assert rec["wait_seconds"] == pytest.approx(0.5)
        assert rec["ttft_seconds"] == pytest.approx(0.5)
        assert rec["tpot_seconds"] == pytest.approx(0.5)   # 2.0s / 4 gaps
        assert rec["e2e_seconds"] == pytest.approx(2.5)
        assert rec["tokens"] == 5 and rec["prompt_tokens"] == 10
        assert rec["cached_tokens"] == 8

    def test_record_rejects_unfinished(self):
        r = Request(rid=0, prompt=np.arange(4), max_new=2)
        with pytest.raises(ValueError, match="not completed"):
            request_record(r)

    def test_breakdown_orders_by_rid(self):
        reqs = [self._req(2, 0, 0, 0, 1, 2), self._req(0, 0, 0, 0, 1, 2),
                self._req(1, 0, 0, 0, 1, 2)]
        assert [r["rid"] for r in per_request_breakdown(reqs)] == [0, 1, 2]

    def test_aggregate_attainment_and_goodput(self):
        # interactive SLO: ttft <= 0.5, tpot <= 0.1
        recs = per_request_breakdown([
            self._req(0, 0.0, 0.1, 0.1, 0.5, 5),    # ttft .1 tpot .1  meets
            self._req(1, 0.0, 1.0, 1.0, 1.4, 5),    # ttft 1.0         misses
            self._req(2, 0.0, 0.2, 0.2, 4.2, 5),    # tpot 1.0         misses
        ])
        agg = aggregate_requests(recs, SLO, makespan_s=10.0)
        bucket = agg["slo"]["interactive"]
        assert bucket["requests"] == 3 and bucket["met"] == 1
        assert bucket["attainment"] == pytest.approx(1 / 3)
        assert agg["slo_attainment"] == pytest.approx(1 / 3)
        assert agg["tokens"] == 15 and agg["slo_met_tokens"] == 5
        assert agg["goodput_tokens_per_s"] == pytest.approx(1.5)
        assert agg["goodput_under_slo_tokens_per_s"] == pytest.approx(0.5)

    def test_aggregate_unknown_class_cannot_meet(self):
        recs = per_request_breakdown(
            [self._req(0, 0.0, 0.1, 0.1, 0.2, 3, slo="no-such-class")])
        agg = aggregate_requests(recs, SLO)
        assert agg["slo"]["unclassified"]["met"] == 0
        assert agg["slo_attainment"] == 0.0

    def test_session_per_request_delegates(self, setup):
        with session(setup, slots=1) as sess:
            sess.submit(np.arange(12), max_new=3, slo_class="interactive")
            sess.drain()
            recs = sess.per_request()
        assert len(recs) == 1 and recs[0]["slo_class"] == "interactive"
        assert recs[0]["tokens"] == 3


class TestPercentiles:
    def test_known_values(self):
        xs = [1.0, 2.0, 3.0, 4.0, 5.0]
        assert percentile(xs, 0) == 1.0
        assert percentile(xs, 50) == 3.0
        assert percentile(xs, 100) == 5.0
        assert percentile(xs, 75) == 4.0
        assert percentile([7.0], 95) == 7.0

    def test_interpolates_like_numpy(self):
        rng = np.random.default_rng(0)
        xs = rng.standard_normal(37).tolist()
        for q in (0, 13, 50, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), abs=1e-12)

    def test_percentiles_keys_and_empty(self):
        assert set(percentiles([1.0, 2.0])) == {"p50", "p95", "p99"}
        assert percentiles([]) == {}
        with pytest.raises(ValueError):
            percentile([], 50)
        with pytest.raises(ValueError):
            percentile([1.0], 101)

    def test_summarize_steps_reports_step_tails(self, setup):
        from repro.core.engine import summarize_steps

        with session(setup, slots=1) as sess:
            sess.submit(np.arange(16), max_new=6)
            sess.drain()
            rep = summarize_steps(sess.engine.step_log)
        assert {"step_seconds_p50", "step_seconds_p95",
                "step_seconds_p99"} <= set(rep)
        assert rep["step_seconds_p50"] <= rep["step_seconds_p95"] \
            <= rep["step_seconds_p99"]


def test_segment_seed_stride_collision_free():
    seeds = trace_mod._SegmentSeeds(7)
    a = [seeds.next() for _ in range(100)]
    b = [trace_mod._SegmentSeeds(8).next()]
    assert len(set(a)) == 100
    assert not set(a) & set(b)


def test_slo_class_is_frozen_value_type():
    c = SLOClass("x", 1.0, 2.0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        c.ttft_s = 3.0
    assert c.to_dict() == {"ttft_s": 1.0, "tpot_s": 2.0}
