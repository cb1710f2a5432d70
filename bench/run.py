#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process: turn on JAX's compilation cache inside the
checkout, name the device, make the configuration's model and weights on
it (its family's module, found by name in ``bench/families/``), warm
every shape the cell's traffic uses (the traffic kind's module, found by
name in ``bench/kinds/``, draws its requests from the seed), run the
traffic for ``--seconds``, check the
served tokens against the plain reference, and print one JSON line last on
standard output.  ``--trace 0`` reports the cell's end-to-end metrics and
``--trace 1`` its per-layer metrics, from a profiler trace of a few seconds
in the middle of the window.  Without an accelerator, or with fewer chips
than the cell asks for, it exits nonzero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    cell: str
    config: dict
    engine: dict
    traffic: dict
    setup_s: float
    t_open: float
    t_close: float
    requests: list
    decode: object          # serve.Spans of decode_step
    before: dict
    after: dict
    io_wait_s: float        # measured fetch wait of the window's decode steps
    trace: dict | None      # devtrace.reduce of the traced part, if traced
    trace_bounds: tuple | None
    peak: dict | None


def enable_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    whatever the environment says, keeping every program and evicting
    none: with a size limit JAX lists and reads the whole directory on
    every write, which on a cold run of hundreds of small programs costs
    more than compiling them."""
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)


def fit_adapter(model, params, cfg, layer, rank):
    """KVSwap's offline adapter: SVD of the family's calibration layer's K
    on a calibration prompt drawn from the configuration's weight seed, so
    every run fits the same."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.lowrank import fit_adapter as fit

    n = cfg["calibration_tokens"]
    calib = np.random.default_rng(cfg["weights"]["seed"]).integers(0, model.vocab_size, (1, n))
    x = model.embed(params, jnp.asarray(calib))
    pos = jnp.arange(n)[None]
    for i in range(layer):
        x = model.prefill_block(params, i, x, pos)[0]
    _, k, _ = model.prefill_block(params, layer, x, pos)
    return fit(np.asarray(k), rank=rank)


@dataclasses.dataclass
class Session:
    """One cell's served path, built from its configuration."""

    params: dict
    sess: object            # repro.serving.api.ServeSession
    prefix_cache: object
    drv: object             # serve.Client
    compiles: object        # serve.CompileCounter

    def close(self) -> None:
        self.sess.close()
        self.prefix_cache.close()


def build_session(cell) -> Session:
    """The configuration's weights on the device, the low-rank adapter, and a
    ``ServeSession`` with a prefix cache, at the cell's settings; every
    other engine switch stays at the program's default.  The model, its
    weights and the calibration layer come from the configuration's
    family."""
    from repro.cache import PrefixCache, PrefixCacheConfig
    from repro.core.engine import EngineConfig
    from repro.serving.api import ServeSession

    from bench import serve, spec

    cfg, traffic, eng = cell.config, cell.traffic, cell.engine
    family = spec.family(cfg["family"])
    compiles = serve.CompileCounter()
    params = family.params(cfg)
    model = family.model(cfg)
    adapter = fit_adapter(model, params, cfg, family.calibration_layer(cfg), eng["rank"])
    ecfg = EngineConfig(group_size=eng["group_size"], n_select=eng["n_select"],
                        rank=eng["rank"], reuse_capacity=eng["reuse_capacity"],
                        kv_bits=eng["kv_bits"], warm_budget_bytes=eng["warm_budget_bytes"],
                        max_seq=traffic["max_seq"], dtype=cfg["torch_dtype"])
    pc = PrefixCache(PrefixCacheConfig(block_tokens=traffic["prefix_block_tokens"],
                                       budget_bytes=traffic["prefix_budget_bytes"]))
    sess = ServeSession(model, params, ecfg, slots=traffic["slots"], adapter=adapter,
                        prefix_cache=pc)
    return Session(params, sess, pc, serve.Client(sess), compiles)


def serve_cell(cell, seed: int, seconds: float, trace: bool, peak: dict | None):
    """Set up, run the window through the cell's traffic kind, and return
    the run record, the weights and the served requests kept for the check,
    the count of requests attempted and failed, and the device's peak
    memory."""
    import jax

    from bench import serve, spec

    built = build_session(cell)
    sess, drv, compiles = built.sess, built.drv, built.compiles
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    tracer = serve.Tracer(logdir, 0.0, seconds, min(TRACE_SECONDS, seconds / 3))
    win = spec.kind(cell.traffic["kind"]).window(drv, compiles, cell, seed, seconds, tracer)
    before = win.before
    after = drv.snapshot(compiles)
    io_wait = sum(s.io_wait_seconds for s in sess.engine.step_log[before["steps"]:after["steps"]])
    log(f"compiles: set-up {before['compiles']} ({compiles.cache_hits} from the cache, "
        f"{compiles.seconds:.3f} s), window {after['compiles'] - before['compiles']}")
    log_counts(before, after)
    dev = jax.devices()[0]
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    trace_red = None
    if logdir is not None:
        from bench import devtrace

        paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
        if paths:
            trace_red = devtrace.reduce(devtrace.extract(paths[0]))
        shutil.rmtree(logdir, ignore_errors=True)
    run = Run(cell=cell.name, config=cell.config, engine=cell.engine, traffic=cell.traffic,
              setup_s=win.t_open - T_START, t_open=win.t_open, t_close=win.t_close,
              requests=win.requests, decode=drv.decode, before=before,
              after=after, io_wait_s=io_wait, trace=trace_red,
              trace_bounds=tracer.bounds, peak=peak)
    failed = sum(1 for r in win.attempted if r.failed)
    built.close()
    params = built.params
    del built, sess, drv
    gc.collect()
    return run, params, win.served, len(win.attempted), failed, memory_peak


def log_counts(before: dict, after: dict) -> None:
    """Where the disk tier's bytes came from in the window (the program's
    own count, beside what the kernel says reached a block device), and
    how much each step's selection overlapped the step before."""
    from bench import serve

    tmp = tempfile.gettempdir()
    acct = after["read_bytes"] - before["read_bytes"]
    msg = (f"storage: store under {tmp} ({serve.fs_type(tmp)}); window: disk tier "
           f"read_bytes {acct}")
    if before["proc_io"] and after["proc_io"]:
        delta = {k: after["proc_io"][k] - before["proc_io"][k]
                 for k in ("read_bytes", "write_bytes")}
        msg += (f", process read_bytes {delta['read_bytes']} and write_bytes "
                f"{delta['write_bytes']} (/proc/self/io: block-device bytes only)")
    log(msg)
    prev = after["select_prev"] - before["select_prev"]
    hits = after["reuse_hits"] - before["reuse_hits"]
    looked = hits + after["reuse_misses"] - before["reuse_misses"]
    if prev and looked:
        shared = after["select_shared"] - before["select_shared"]
        log(f"selection: window step-to-step overlap {100.0 * shared / prev:.2f} %, "
            f"reuse-buffer hits {100.0 * hits / looked:.2f} %")


def main(argv=None, *, cell=None, require_accelerator: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec

    if cell is None:
        cell = spec.cell(args.workload)
    if require_accelerator:
        enable_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, count {len(devs)}",
          flush=True)
    if require_accelerator and dev.platform != "tpu":
        log(f"no TPU found (JAX platform {dev.platform!r}); nothing measured")
        return 3
    if len(devs) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, found {len(devs)}")
        return 3
    peak = spec.peaks(dev.device_kind) if require_accelerator else None

    from bench import compare

    run, params, served, attempted, failed, memory_peak = serve_cell(
        cell, args.seed, args.seconds, bool(args.trace), peak)
    log(f"window {run.t_close - run.t_open:.3f} s, setup {run.setup_s:.3f} s, "
        f"requests attempted {attempted}, failed {failed}")

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = spec.reference(cell.config["reference"])
    sample = compare.sample(served, cell.checks["sample_requests"], args.seed)
    gaps = compare.served_gaps(ref, params, cell.config, sample)
    numbers = compare.numbers(gaps)
    n_tokens = int(gaps.size)
    log(f"reference: {len(sample)} requests, {n_tokens} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s; widest gap {numbers['gap_max']} (not compared)")
    correct, checks = compare.judge(numbers, failed, n_tokens, cell.checks)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
