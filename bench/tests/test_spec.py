import json
import os

import pytest

from bench import spec


def test_every_cell_resolves_by_name():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert callable(spec.kind(cell.traffic["kind"]).window)
        assert "gap_mean" in cell.checks
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_every_metric_has_a_reader():
    bench = spec.benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_config_files_are_the_ones_named():
    bench = spec.benchmark()
    for c in bench["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
        assert callable(spec.reference(spec.config(c["name"])["reference"]).hidden)


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.cell("no-such.cell")
    with pytest.raises(KeyError):
        spec.peaks("TPU v0")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.kind("no_such_kind")


def test_a_mix_of_a_known_kind_is_data_alone(tmp_path, monkeypatch):
    """A new mix file of a kind already there needs no code: the cell finds
    the kind's module by the name in the file."""
    bench = spec.benchmark()
    w = dict(bench["workloads"][0], name="qwen3-8b.other-mix", traffic="other-mix")
    bench["workloads"].append(w)
    src = os.path.join(spec.BENCH_DIR, "traffic", bench["workloads"][0]["traffic"] + ".json")
    with open(src) as f:
        mix = dict(json.load(f), contexts=[1024, 2048])
    (tmp_path / "other-mix.json").write_text(json.dumps(mix))
    real = spec.traffic
    monkeypatch.setattr(spec, "traffic", lambda name: real(name) if name != "other-mix"
                        else json.loads((tmp_path / "other-mix.json").read_text()))
    monkeypatch.setattr(spec, "checks", lambda name: {})
    cell = spec.cell("qwen3-8b.other-mix", bench)
    assert cell.traffic["contexts"] == [1024, 2048]
    assert callable(spec.kind(cell.traffic["kind"]).window)
