#!/usr/bin/env python3
"""Run one cell in sets of runs and print each metric's spread per set.

    python3 bench/sets.py --workload <cell> --seeds <a,b,...> --out <dir>
        [--sets 2] [--seconds 50] [--trace 0]

Every run is this checkout's ``bench/run.py`` in a process of its own,
one after another, with the same seeds in every set.  Before each run one
line gives the host's load average, its available memory and page cache,
and the free space of the temporary directory, so a drift over the sets
can be told from the seeds; each run's output and errors go to
``<dir>/<set>_<seed>.{out,err}``.  Per metric and set of three runs or
more it prints the median, the spread (``stats.spread``), the spread
without the run farthest from the median, and the ratio of the set's
median to the first set's.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import stats  # noqa: E402


def host() -> str:
    with open("/proc/loadavg") as f:
        load = f.read().split()[0]
    with open("/proc/meminfo") as f:
        mem = {k: int(v.split()[0]) >> 20 for k, v in (line.split(":") for line in f)}
    free = shutil.disk_usage(tempfile.gettempdir()).free >> 30
    return (f"load {load} avail {mem['MemAvailable']} GiB cached {mem['Cached']} GiB "
            f"tmp_free {free} GiB")


def report(readings: list[dict[str, list[float]]]) -> list[str]:
    """One line per metric and set of three runs or more."""
    lines = []
    for m in readings[0]:
        first = statistics.median(readings[0][m])
        for k, r in enumerate(readings, 1):
            xs = r.get(m, [])
            if len(xs) < 3:
                continue
            med = statistics.median(xs)
            lines.append(f"{m} set {k}: median {med!r} spread {stats.spread(xs)!r} "
                         f"trimmed {stats.trimmed_spread(xs)!r} vs set 1 {med / first!r}")
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, default=50)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(args.out, exist_ok=True)
    readings: list[dict[str, list[float]]] = []
    for k in range(1, args.sets + 1):
        readings.append({})
        for seed in seeds:
            print(f"set {k} seed {seed} before: {host()}", flush=True)
            base = os.path.join(args.out, f"{k}_{seed}")
            t0 = time.perf_counter()
            with open(base + ".out", "w") as out, open(base + ".err", "w") as err:
                rc = subprocess.call(
                    [sys.executable, "bench/run.py", "--workload", args.workload, "--seed",
                     str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace)],
                    cwd=ROOT, stdout=out, stderr=err)
            with open(base + ".out") as f:
                last = (f.read().strip().splitlines() or [""])[-1]
            res = json.loads(last) if rc == 0 and last.startswith("{") else {}
            values = {m: v["value"] for m, v in res.get("metrics", {}).items()}
            for m, v in values.items():
                readings[-1].setdefault(m, []).append(v)
            print(f"set {k} seed {seed} rc {rc} wall {time.perf_counter() - t0:.1f} s "
                  f"correct {res.get('correct')} {json.dumps(values)} "
                  f"checks {json.dumps(res.get('checks'))}", flush=True)
    print("\n".join(report(readings)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
