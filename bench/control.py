#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3]

For each seed, in one process: run the cell as ``bench/run.py`` does (a
short window at the cell's own load), then read the gaps of the served
tokens under the float32 reference (the program's reading) and, for the
seeds in ``--control-seeds``, at the same positions the gaps of the tokens
that the float8 reference ranks first (the control's reading).  One JSON
line per seed, with the window's metrics beside the readings.  Benchmark
runs do not run this.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seed: int, seconds: float, peak=None, control: bool = True) -> dict:
    from bench import compare, run, spec

    t_start = time.perf_counter()
    rec, params, served, attempted, failed, _ = run.serve_cell(cell, seed, seconds, False, peak)
    metrics = {}
    for m in cell.end_to_end + cell.per_layer:
        value = spec.metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = value
    ref = spec.reference(cell.config["reference"])
    sample = compare.sample(served, cell.checks["sample_requests"], seed)
    t0 = time.perf_counter()
    per_req = [compare.served_gaps(ref, params, cell.config, [r]) for r in sample]
    gaps = np.concatenate(per_req) if per_req else np.zeros(0)
    t1 = time.perf_counter()
    # the set-up turns' tokens (prefill) apart from the window's (decode)
    n_early = [len(r.earlier or ()) for r in sample]
    early = np.concatenate([g[:n] for g, n in zip(per_req, n_early)] or [np.zeros(0)])
    late = np.concatenate([g[n:] for g, n in zip(per_req, n_early)] or [np.zeros(0)])
    out = {"seed": seed, "program": compare.numbers(gaps), "tokens": int(gaps.size),
           "prefill": compare.numbers(early), "decode": compare.numbers(late),
           "nonzero_share": float(np.mean(gaps > 0)) if gaps.size else None,
           "requests": len(sample), "failed": failed, "attempted": attempted,
           "metrics": metrics, "serve_s": t0 - t_start, "reference_s": t1 - t0}
    if control:
        out["control"] = compare.numbers(compare.control_gaps(ref, params, cell.config, sample))
        out["control_s"] = time.perf_counter() - t1
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None,
                    help="seeds that also read the control (default: all)")
    ap.add_argument("--engine", nargs="*", default=[], metavar="KEY=INT",
                    help="engine settings to override, for a witness run")
    args = ap.parse_args(argv)
    from bench import run, spec

    cell = spec.cell(args.workload)
    if args.engine:
        over = {k: int(v) for k, v in (e.split("=", 1) for e in args.engine)}
        cell.traffic["engine"] = {**cell.traffic.get("engine", {}), **over}
    run.enable_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU found (JAX platform {dev.platform!r})", file=sys.stderr)
        return 3
    peak = spec.peaks(dev.device_kind)
    ctl = set(args.seeds if args.control_seeds is None else args.control_seeds)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds, peak, seed in ctl)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
