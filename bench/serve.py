"""Drive ``ServeSession`` on the wall clock and stamp what it emits.

The window's entry is ``ServeSession.submit`` plus ``ServeSession.step``.
Every admit, token and finish event is stamped with ``time.perf_counter()``
when the ``step()`` that produced it returns; nothing reads the session's
modeled clock or a modeled ``StepStats`` field.  ``admit_row`` and
``decode_step`` of the session's engine are wrapped on the instance with
timers that end when the returned logits are ready, and with
``TraceAnnotation``s so a trace can name what the host did in a device gap.
How requests are offered is a traffic kind's, in ``bench/kinds/<kind>.py``.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new: int
    admit_t: float | None = None
    slot: int | None = None
    token_t: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finish_t: float | None = None
    failed: str | None = None
    # tokens served earlier for prefixes of this prompt: (position whose
    # logits chose it, token)
    earlier: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    """What a traffic kind hands back once its window has closed."""

    before: dict            # Client.snapshot at the window's open
    t_open: float
    t_close: float
    attempted: list         # requests due in the window
    served: list            # requests the check may sample
    requests: list          # requests the metric readers see


class Spans:
    """Wall-clock spans of one engine method, wrapped on the instance."""

    def __init__(self, obj, method: str, name: str):
        self.spans: list[tuple[float, float]] = []
        orig = getattr(obj, method)

        def timed(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                t0 = time.perf_counter()
                out = jax.block_until_ready(orig(*args, **kwargs))
                self.spans.append((t0, time.perf_counter()))
            return out

        setattr(obj, method, timed)

    def within(self, t0: float, t1: float) -> list[float]:
        return [b - a for a, b in self.spans if t0 <= b <= t1]


class CompileCounter:
    """Programs built for the backend (a persistent-cache load counts too),
    from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Tracer:
    """Profiles ``seconds`` from the middle of the window into ``logdir``."""

    def __init__(self, logdir: str | None, t_open: float, window: float, seconds: float):
        self.logdir = logdir
        self.t_start = t_open + max(0.0, (window - seconds) / 2)
        self.t_stop = self.t_start + seconds
        self.span = None
        self.bounds: tuple[float, float] | None = None

    def tick(self, now: float) -> None:
        if self.logdir is None:
            return
        if self.span is None and self.bounds is None and now >= self.t_start:
            jax.profiler.start_trace(self.logdir)
            self.span = jax.profiler.TraceAnnotation("bench.traced_window")
            self.span.__enter__()
            self.bounds = (time.perf_counter(), None)
        elif self.span is not None and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        if self.span is not None:
            self.bounds = (self.bounds[0], time.perf_counter())
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()


class Client:
    def __init__(self, sess):
        self.sess = sess
        self.reqs: dict[int, Req] = {}
        self.rejected: list[Req] = []
        self.admit = Spans(sess.engine, "admit_row", "bench.admit_row")
        self.decode = Spans(sess.engine, "decode_step", "bench.decode_step")

    def submit(self, prompt, max_new) -> Req:
        from repro.serving.errors import RequestRejected

        try:
            rid = self.sess.submit(prompt, max_new)
        except RequestRejected as exc:
            req = Req(rid=-1, prompt=prompt, max_new=max_new, failed=str(exc))
            self.rejected.append(req)
            return req
        req = Req(rid=rid, prompt=np.asarray(prompt), max_new=max_new)
        self.reqs[rid] = req
        return req

    def step(self) -> float:
        with jax.profiler.TraceAnnotation("bench.session_step"):
            events = self.sess.step()
        t = time.perf_counter()
        for e in events:
            req = self.reqs.get(e.get("rid"))
            if req is None:
                continue
            kind = e["type"]
            if kind == "admit":
                req.admit_t = t
                req.slot = e["slot"]
            elif kind == "token":
                req.token_t.append(t)
                req.tokens.append(int(e["token"]))
            elif kind == "finish":
                req.finish_t = t
            elif kind == "fail":
                req.failed = e.get("error", "failed")
        return t

    def drain(self) -> None:
        while self.sess.has_work:
            self.step()

    def snapshot(self, compiles: CompileCounter) -> dict:
        eng = self.sess.engine
        stats = self.sess.stats()
        return {
            "t": time.perf_counter(),
            "prompt_tokens": stats["prompt_tokens"],
            "cached_prompt_tokens": stats["cached_prompt_tokens"],
            "read_bytes": eng.accountant.snapshot()["read_bytes"],
            "reuse_hits": sum(r.stats.hits for r in eng.reuse),
            "reuse_misses": sum(r.stats.misses for r in eng.reuse),
            "select_shared": sum(s.pred_shared_groups for s in eng.step_log),
            "select_prev": sum(s.pred_prev_groups for s in eng.step_log),
            "steps": len(eng.step_log),
            "compiles": compiles.count,
            "proc_io": proc_io(),
        }


def proc_io() -> dict | None:
    """This process's I/O counters from ``/proc/self/io``: ``read_bytes``
    and ``write_bytes`` count only what reached a block device, not
    page-cache hits."""
    try:
        with open("/proc/self/io") as f:
            return {k: int(v) for k, v in (line.split(": ") for line in f)}
    except OSError:
        return None


def fs_type(path: str) -> str:
    """The type of the filesystem that holds ``path``, from ``/proc/mounts``."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                mnt, typ = line.split()[1:3]
                inside = path == mnt or path.startswith(mnt.rstrip("/") + "/")
                if inside and len(mnt) > len(best):
                    best, kind = mnt, typ
    except OSError:
        pass
    return kind
