"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout root names the cells; each cell names a
configuration and a traffic mix, and each metric name is a reader file.
Everything that belongs to one of them lives in a file of its own:

    bench/configs/<config>.json      sizes, engine settings, weight structure
    bench/families/<family>.py       what the harness knows of an architecture,
                                     named by a config: model, weights, counts
    bench/traffic/<traffic>.json     parameters of one mix, with its ``kind``
    bench/kinds/<kind>.py            how a kind of mix is offered: window(...)
    bench/checks/<cell>.json         limits of the numbers `correct` compares
    bench/metrics/<metric>.py        reader: run -> float | None
    bench/reference/<name>.py        plain reference named by a config

A later cell, configuration, family, mix or metric is added by adding
files and entries; a mix of a kind already there is a data file alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def config(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def checks(cell: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "checks", f"{cell}.json"))


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")


def _load_file(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    return _load_file(path, "bench_metric_" + name.replace(".", "_")).read


def kind(name: str):
    """The module ``bench/kinds/<name>.py`` that offers a kind of mix."""
    path = os.path.join(BENCH_DIR, "kinds", f"{name}.py")
    return _load_file(path, "bench_kind_" + name)


def family(name: str):
    """The module ``bench/families/<name>.py`` of a model family."""
    path = os.path.join(BENCH_DIR, "families", f"{name}.py")
    return _load_file(path, "bench_family_" + name)


def reference(name: str):
    path = os.path.join(BENCH_DIR, "reference", f"{name}.py")
    return _load_file(path, "bench_reference_" + name)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    checks: dict

    @property
    def engine(self) -> dict:
        """Engine settings: the configuration's, overridden by the mix's."""
        return {**self.config.get("engine", {}), **self.traffic.get("engine", {})}


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    return Cell(
        name=name, config=config(entry["config"]),
        traffic=traffic(entry["traffic"]), chips=int(entry["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        checks=checks(name))
