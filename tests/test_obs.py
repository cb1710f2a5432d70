"""Observability subsystem (PR 7): spans, metrics, prefetch quality.

The contracts pinned here:

* Perfetto export passes the ``trace_event`` schema check and a serving
  replay covers every lane family (engine steps, per-layer ops, prefetch
  workers, request lifecycle, modeled compute/io recurrence);
* registry totals agree **exactly** with the legacy stats dicts
  (``IOAccountant.snapshot()``, ``step_log``/``summarize_steps``) — the
  "thin views, byte-compatible" promise;
* the disabled path is a true no-op: identical token streams with obs on
  vs off across ``device_resident`` × ``async_io``, zero spans / empty
  registry without a handle, and near-zero per-call overhead;
* ``ServeSession.stats()`` exposes the two distinct warm-bytes keys
  (session-cumulative ``warm_bytes`` vs mean ``warm_bytes_per_step``);
* the decode step's phases reach the profiler's clock as ``kvswap.*``
  annotations with no handle at all, nested as docs/observability.md
  ("Profiler spans") says.
"""

import glob
import json
import time

import jax
import numpy as np
import pytest

from repro.core.engine import EngineConfig, KVSwapEngine, summarize_steps
from repro.obs import (MODEL_PID, WALL_PID, MetricsRegistry, NULL_OBS,
                       Observability, PrefetchQualityMeter, SpanTracer,
                       validate_trace_events)
from repro.obs.report import main as report_main
from repro.serving.api import ServeSession


# ---------------------------------------------------------------- metrics

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("g")
    g.set(7)
    g.dec(3)
    assert g.value == 4

    h = reg.histogram("h_seconds")
    for v in (3.0, 1.0, 2.0):
        h.observe(v)
    assert h.count == 3 and h.sum == 6.0
    assert h.percentiles()["p50"] == 2.0


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert "x" in reg and len(reg) == 1
    assert reg.get("missing") is None


def test_snapshot_and_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("b_total", "a counter").inc(5)
    reg.gauge("a_gauge").set(2)
    h = reg.histogram("lat_seconds", "latency")
    h.observe(0.5)
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)          # deterministic order
    assert snap["b_total"] == 5
    assert snap["lat_seconds"]["count"] == 1
    assert snap["lat_seconds"]["p95"] == 0.5
    text = reg.to_prometheus()
    assert "# TYPE b_total counter" in text
    assert "# HELP lat_seconds latency" in text
    assert '# TYPE lat_seconds summary' in text
    assert 'lat_seconds{quantile="0.5"} 0.5' in text
    assert "lat_seconds_count 1" in text
    assert text.endswith("\n")


def test_registry_instance_labels_disambiguate_series():
    """Two labeled registries (a fleet) create disjoint series under the
    same metric names; their snapshots merge without collisions."""
    regs = {name: MetricsRegistry(labels={"replica": name})
            for name in ("r0", "r1")}
    for i, reg in enumerate(regs.values()):
        reg.counter("kvswap_io_read_bytes_total", "bytes").inc(10 * (i + 1))
    snaps = [r.snapshot() for r in regs.values()]
    assert list(snaps[0]) == ['kvswap_io_read_bytes_total{replica="r0"}']
    merged = {**snaps[0], **snaps[1]}
    assert len(merged) == 2
    assert merged['kvswap_io_read_bytes_total{replica="r0"}'] == 10
    assert merged['kvswap_io_read_bytes_total{replica="r1"}'] == 20
    # exposition: one TYPE header per family, labels on each sample
    text = regs["r0"].to_prometheus()
    assert "# TYPE kvswap_io_read_bytes_total counter" in text
    assert 'kvswap_io_read_bytes_total{replica="r0"} 10' in text
    # per-call labels merge with (and override nothing in) the defaults
    reg = regs["r0"]
    reg.counter("x_total", labels={"reason": "overload"}).inc()
    assert reg.get("x_total", labels={"reason": "overload"}).value == 1
    assert reg.snapshot()['x_total{reason="overload",replica="r0"}'] == 1


def test_registry_unlabeled_snapshot_byte_identical():
    """The zero-label path renders bare names — a single-replica process
    exports exactly the historical format."""
    import json

    def build(reg):
        reg.counter("b_total", "a counter").inc(5)
        reg.histogram("lat_seconds", "latency").observe(0.5)
        return reg

    plain = build(MetricsRegistry())
    defaulted = build(MetricsRegistry(labels=None))
    assert json.dumps(plain.snapshot(), sort_keys=True) \
        == json.dumps(defaulted.snapshot(), sort_keys=True)
    assert plain.to_prometheus() == defaulted.to_prometheus()
    assert "lat_seconds_count 1" in plain.to_prometheus()


def test_registry_label_validation():
    with pytest.raises(ValueError):
        MetricsRegistry(labels={"bad key!": "v"})
    with pytest.raises(ValueError):
        MetricsRegistry(labels={"k": 'quote"inside'})
    hist = MetricsRegistry(labels={"replica": "r0"}).histogram("h_seconds")
    hist.observe(1.0)
    assert hist.labels == {"replica": "r0"}


def test_labeled_histogram_prometheus_quantiles():
    reg = MetricsRegistry(labels={"replica": "r9"})
    reg.histogram("lat_seconds", "latency").observe(0.5)
    text = reg.to_prometheus()
    assert 'lat_seconds{quantile="0.5",replica="r9"} 0.5' in text
    assert 'lat_seconds_sum{replica="r9"} 0.5' in text
    assert 'lat_seconds_count{replica="r9"} 1' in text


# ------------------------------------------------------------------ spans

def test_tracer_disabled_records_nothing():
    tr = SpanTracer(enabled=False)
    tr.add("a", "t", wall_t0=0.0, wall_dur=1.0)
    assert len(tr) == 0


def test_tracer_dual_clock_export_and_validation(tmp_path):
    tr = SpanTracer()
    tr.add("both", "lane", wall_t0=0.0, wall_dur=0.5,
           model_t0=1.0, model_dur=0.25, args={"k": 1})
    tr.add("wall_only", "lane", wall_t0=0.5, wall_dur=0.1)
    tr.add("mark", "lane", model_t0=2.0, instant=True)
    with tr.wall_span("scoped", "other") as sc:
        sc.args["n"] = 3
    path = tmp_path / "t.json"
    obj = SpanTracer.export(tr, path)
    loaded = json.loads(path.read_text())
    assert loaded == obj
    info = validate_trace_events(obj)
    # dual-clock span lands once per clock; metadata names both processes
    assert info["processes"] == {WALL_PID: "wall clock",
                                 MODEL_PID: "modeled clock"}
    names = [e["name"] for e in obj["traceEvents"] if e["ph"] == "X"]
    assert names.count("both") == 2
    assert info["complete_events"] == 4        # both×2 + wall_only + scoped
    instants = [e for e in obj["traceEvents"] if e["ph"] == "i"]
    assert len(instants) == 1 and instants[0]["s"] == "t"
    scoped = [e for e in obj["traceEvents"] if e["name"] == "scoped"]
    assert scoped[0]["args"] == {"n": 3}


def test_validate_trace_events_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace_events({"no": "traceEvents"})
    with pytest.raises(ValueError):               # X without dur
        validate_trace_events([
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "t"}},
            {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0},
        ])
    with pytest.raises(ValueError):               # X on an unnamed track
        validate_trace_events([
            {"name": "a", "ph": "X", "pid": 1, "tid": 9, "ts": 0, "dur": 1},
        ])
    with pytest.raises(ValueError):               # no complete events at all
        validate_trace_events([
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "p"}},
        ])


# ---------------------------------------------------------------- quality

class _FakeReuse:
    def __init__(self, res):
        self._res = res

    def resident(self, bi):
        return set(self._res[bi])


def test_quality_meter_precision_recall_staleness():
    q = PrefetchQualityMeter()
    ids = np.array([[0, 1, 2, 3]])
    mask = np.ones((1, 4), dtype=bool)
    q.begin_step()
    q.observe(0, ids, mask, _FakeReuse({0: {0, 1}}))
    first = q.finish_step()
    assert first.prev_groups == 0               # nothing to score against yet
    assert first.resident_groups == 2 and first.stale_groups == 0

    q.begin_step()
    q.observe(0, np.array([[2, 3, 4, 5]]), mask,
              _FakeReuse({0: {0, 1, 2, 3}}))    # 0,1 resident but unselected
    c = q.finish_step()
    assert (c.shared_groups, c.prev_groups, c.cur_groups) == (2, 4, 4)
    assert (c.stale_groups, c.resident_groups) == (2, 4)

    # empty-mask rows are skipped entirely
    q.begin_step()
    q.observe(0, ids, np.zeros((1, 4), dtype=bool), _FakeReuse({0: {7}}))
    c = q.finish_step()
    assert c.cur_groups == 0 and c.resident_groups == 0

    # a retired slot's history must not score against the next tenant
    q.clear_row(0)
    q.begin_step()
    q.observe(0, ids, mask)
    assert q.finish_step().prev_groups == 0


def test_quality_ratios_pool_in_summarize_steps():
    from repro.core.engine import StepStats
    steps = [StepStats(pred_shared_groups=2, pred_prev_groups=4,
                       pred_cur_groups=8, stale_groups=1, resident_groups=2),
             StepStats(pred_shared_groups=6, pred_prev_groups=4,
                       pred_cur_groups=8, stale_groups=0, resident_groups=2)]
    s = summarize_steps(steps)
    assert s["pred_precision"] == 8 / 8         # ratio of sums, not mean of ratios
    assert s["pred_recall"] == 8 / 16
    assert s["stale_group_rate"] == 1 / 4
    assert steps[0].pred_precision == 0.5 and steps[0].pred_recall == 0.25


# ------------------------------------------------- engine <-> registry

def _engine_cfg(**kw):
    base = dict(group_size=4, n_select=4, rank=8, reuse_capacity=6,
                max_seq=128)
    base.update(kw)
    return EngineConfig(**base)


def _run_engine(tiny_adapter, tiny_params, rng, *, obs=None, steps=6, **kw):
    prompt = np.asarray(rng.integers(0, 97, (2, 33)), dtype=np.int32)
    calib = rng.standard_normal((1, 64, 2, 16)).astype(np.float32)
    with KVSwapEngine(tiny_adapter, tiny_params, _engine_cfg(**kw), batch=2,
                      calib_k=calib, obs=obs) as eng:
        toks = eng.generate(prompt, steps)
        return np.asarray(toks), eng.accountant.snapshot(), list(eng.step_log)


def test_registry_totals_match_accountant_and_steps_exactly(
        tiny_adapter, tiny_params):
    rng = np.random.default_rng(7)
    obs = Observability()
    _, snap, steps = _run_engine(tiny_adapter, tiny_params, rng, obs=obs,
                                 async_io=True, warm_budget_bytes=1 << 16,
                                 kv_bits=8)
    reg = obs.registry
    # bit-equal by construction: mirrored inside the accountant's lock
    assert reg.get("kvswap_io_read_bytes_total").value == snap["read_bytes"]
    assert reg.get("kvswap_io_read_requests_total").value == snap["read_requests"]
    assert reg.get("kvswap_io_read_seconds_total").value == snap["read_seconds"]
    assert reg.get("kvswap_io_write_bytes_total").value == snap["write_bytes"]
    assert reg.get("kvswap_warm_served_bytes_total").value == snap["warm_bytes"]
    # per-step histograms observe step_log in append order
    assert reg.get("kvswap_engine_decode_steps_total").value == len(steps)
    assert reg.get("kvswap_engine_decode_tokens_total").value == \
        sum(s.active_rows for s in steps)
    hist = reg.get("kvswap_step_pipelined_seconds")
    assert hist.samples() == [s.pipelined_seconds for s in steps]
    assert reg.get("kvswap_step_wall_seconds").count == len(steps)


@pytest.mark.parametrize("device_resident", [False, True])
@pytest.mark.parametrize("async_io", [False, True])
def test_tokens_bit_identical_with_obs(tiny_adapter, tiny_params,
                                       device_resident, async_io):
    kw = dict(device_resident=device_resident, async_io=async_io)
    t_off, _, _ = _run_engine(tiny_adapter, tiny_params,
                              np.random.default_rng(3), obs=None, **kw)
    t_on, _, _ = _run_engine(tiny_adapter, tiny_params,
                             np.random.default_rng(3),
                             obs=Observability(), **kw)
    assert np.array_equal(t_off, t_on)


def test_disabled_path_is_a_true_noop(tiny_adapter, tiny_params):
    before = len(NULL_OBS.tracer)
    _run_engine(tiny_adapter, tiny_params, np.random.default_rng(5),
                obs=None, async_io=True)
    # the shared null handle is never written to
    assert len(NULL_OBS.tracer) == before == 0
    assert len(NULL_OBS.registry) == 0
    # per-call overhead of a disabled tracer: one attribute load + bool
    # test.  Budget is deliberately generous (CI noise) — the point is to
    # catch accidental allocation/locking on the disabled path.
    tr = SpanTracer(enabled=False)
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        tr.add("x", "t")
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"disabled add costs {per_call * 1e6:.2f} us"


def test_disabled_phase_is_cheap_and_records_nothing():
    # a phase on the null handle is one profiler annotation with no
    # session: no span, no lock, no registry write
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        with NULL_OBS.phase("fetch", layer=i & 3) as ph:
            ph.note(read_bytes=i)
    per_call = (time.perf_counter() - t0) / n
    assert len(NULL_OBS.tracer) == 0 and len(NULL_OBS.registry) == 0
    assert per_call < 20e-6, f"disabled phase costs {per_call * 1e6:.2f} us"


def test_enabled_phase_records_the_wall_span_under_todays_name():
    obs = Observability()
    with obs.phase("predict", layer=3):
        pass
    with obs.phase("fetch", layer=1) as ph:
        ph.note(read_bytes=64)
    with obs.phase("select_pull", layer=3):   # profiler-only phase
        pass
    spans = obs.tracer.spans()
    assert [(s.name, s.track, s.cat) for s in spans] == [
        ("predict L3", "layer3", "predict"), ("fetch L1", "layer1", "fetch")]
    assert spans[1].args == {"layer": 1, "read_bytes": 64}
    assert all(s.wall_t0 is not None and s.wall_dur >= 0 for s in spans)


# ------------------------------------------------- serving replay trace

def _serve(tiny_adapter, tiny_params, obs):
    rng = np.random.default_rng(11)
    calib = rng.standard_normal((1, 64, 2, 16)).astype(np.float32)
    cfg = _engine_cfg(async_io=True, warm_budget_bytes=1 << 16, kv_bits=8)
    with ServeSession(tiny_adapter, tiny_params, cfg, slots=2,
                      calib_k=calib, obs=obs) as ses:
        for i in range(4):
            ses.submit(rng.integers(0, 97, size=13 + i), 5,
                       arrival=i * 0.05)
        ses.drain()
        return ses.stats()


def test_serve_trace_covers_lane_families(tiny_adapter, tiny_params, tmp_path):
    obs = Observability()
    _serve(tiny_adapter, tiny_params, obs)
    obj = obs.export_trace(tmp_path / "trace.json")
    info = validate_trace_events(obj)
    tracks = set(info["tracks"].values())
    # >= 4 distinct lane families on the timeline (acceptance criterion)
    assert "engine-step" in tracks
    assert "requests" in tracks
    assert any(t.startswith("slot") for t in tracks)
    assert any(t.startswith("layer") for t in tracks)
    assert any(t.startswith("kvswap-prefetch-") for t in tracks)
    assert {"compute", "io"} <= tracks          # modeled per-layer recurrence
    # request lifecycle: every request got a queued span, a slot residency
    # span and a first_token instant
    spans = obs.tracer.spans()
    assert sum(1 for s in spans if s.track == "requests") == 4
    assert sum(1 for s in spans if s.name == "first_token") == 4
    # the registry saw the same four completions
    snap = obs.snapshot()
    assert snap["kvswap_requests_completed_total"] == 4
    assert snap["kvswap_request_ttft_seconds"]["count"] == 4


def test_serve_stats_warm_bytes_keys_are_distinct(tiny_adapter, tiny_params):
    st = _serve(tiny_adapter, tiny_params, None)
    # satellite 1: cumulative vs per-step were shadowing each other before
    assert "warm_bytes" in st and "warm_bytes_per_step" in st
    assert st["warm_bytes"] == int(st["warm_bytes"])        # cumulative bytes
    assert st["warm_bytes_per_step"] <= max(st["warm_bytes"], 1)


def test_report_cli(tiny_adapter, tiny_params, tmp_path):
    obs = Observability()
    _serve(tiny_adapter, tiny_params, obs)
    path = str(tmp_path / "trace.json")
    obs.export_trace(path)
    assert report_main([path]) == 0
    assert report_main([path, "--check"]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0}]}))
    assert report_main([str(bad), "--check"]) == 1


# ------------------------------------------------- profiler spans


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; return the ``kvswap.*`` host
    events as ``{line: [(name, t0_ns, t1_ns, stats)]}`` (one line per
    thread)."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("kvswap.")]
            if evs:
                lines[(plane.name, k)] = evs
    return lines


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.mark.parametrize("device_resident", [False, True])
@pytest.mark.parametrize("async_io", [False, True])
def test_decode_phases_reach_the_profiler_without_a_handle(
        tiny_adapter, tiny_params, tmp_path, device_resident, async_io):
    rng = np.random.default_rng(13)
    prompt = np.asarray(rng.integers(0, 97, (2, 33)), dtype=np.int32)
    calib = rng.standard_normal((1, 64, 2, 16)).astype(np.float32)
    cfg = _engine_cfg(device_resident=device_resident, async_io=async_io)
    with KVSwapEngine(tiny_adapter, tiny_params, cfg, batch=2,
                      calib_k=calib) as eng:
        logits = eng.prefill(prompt)
        tok = np.asarray(np.argmax(logits, -1))

        def three_steps():
            nonlocal tok
            for _ in range(3):
                tok = np.asarray(np.argmax(eng.decode_step(tok), -1))

        lines = _profiled(tmp_path, three_steps)
        n_kv = len(eng.kv_layers)
    main = [evs for evs in lines.values()
            if any(e[0] == "kvswap.decode_step" for e in evs)]
    assert len(main) == 1
    main = main[0]
    steps = [e for e in main if e[0] == "kvswap.decode_step"]
    assert len(steps) == 3
    assert [e[3]["step"] for e in steps] == [0, 1, 2]
    fetch = "kvswap.fetch_wait" if async_io else "kvswap.fetch"
    per_layer = ["kvswap.predict", "kvswap.select_pull", fetch, "kvswap.attn"]
    if device_resident:
        per_layer.append("kvswap.mirror_upload")
    children = ("kvswap.select_pull", "kvswap.disk_read")
    for st in steps:
        inner = [e for e in main if e is not st and _inside(e, st)]
        for name in per_layer:
            got = sorted(e[3]["layer"] for e in inner if e[0] == name)
            assert got == sorted(eng.kv_layers), (name, got)
        assert sum(e[0] == "kvswap.step_prologue" for e in inner) == 1
        # the prompt's 33 tokens leave a tail of 1, so the third step
        # completes a group in every row and layer: one step-level spill
        flush = [e for e in inner
                 if e[0] == "kvswap.spill" and "layer" not in e[3]]
        want = [2 * n_kv] if st[3]["step"] == 2 else []
        assert [e[3]["groups"] for e in flush] == want
        assert not any(e[0] == "kvswap.klr_append" for e in inner)
        # select_pull is the predict's child; the rest are siblings that
        # never overlap
        for e in inner:
            if e[0] == "kvswap.select_pull":
                assert any(p[0] == "kvswap.predict" and _inside(e, p)
                           for p in inner)
        sib = sorted((e for e in inner if e[0] not in children),
                     key=lambda e: e[1])
        assert all(a[2] <= b[1] for a, b in zip(sib, sib[1:])), sib
        # the store reads are the fetch's children on whichever thread
        # fetches
        for e in inner:
            if e[0] == "kvswap.disk_read":
                assert not async_io
                assert any(f[0] == fetch and _inside(e, f) for f in inner)
    assert n_kv == 2
    assert any(e[0] == "kvswap.disk_read" for evs in lines.values() for e in evs)
    workers = [evs for evs in lines.values() if evs is not main]
    prefetch = [e for evs in workers for e in evs if e[0] == "kvswap.prefetch"]
    if async_io:
        assert len(prefetch) == 3 * n_kv
        assert not any(e[0] == "kvswap.prefetch" for e in main)
    else:
        assert not prefetch


def test_serve_session_phases_reach_the_profiler(tiny_adapter, tiny_params,
                                                 tmp_path):
    lines = _profiled(tmp_path, lambda: _serve(tiny_adapter, tiny_params, None))
    names = {e[0] for evs in lines.values() for e in evs}
    assert {"kvswap.admit", "kvswap.sample", "kvswap.logits_pull",
            "kvswap.decode_step", "kvswap.prefetch"} <= names
    admits = [e for evs in lines.values() for e in evs if e[0] == "kvswap.admit"]
    assert sorted(e[3]["rid"] for e in admits) == [0, 1, 2, 3]
