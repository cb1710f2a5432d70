import dataclasses
import json
import os
import shutil
import textwrap

import pytest

from bench import run, spec


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
        cfg = spec.config(c["name"])
        assert callable(spec.reference(cfg["reference"]).hidden)
        assert callable(spec.family(cfg["family"]).model)


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.cell("no-such.cell")
    with pytest.raises(KeyError):
        spec.peaks("TPU v0")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.kind("no_such_kind")
    with pytest.raises(FileNotFoundError):
        spec.family("no_such_family")


def test_a_configuration_without_a_family_is_an_error():
    cell = spec.cell("qwen3-8b.longctx-decode")
    del cell.config["family"]
    with pytest.raises(KeyError, match="family"):
        run.build_session(cell)


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


@pytest.mark.parametrize("seeds", [(1, 2), (2216001611, 2**31 + 7)])
def test_every_seed_decodes_the_same_contexts_in_another_order(seeds):
    """The contexts come from the mix's ``content_seed``, so every run does
    the same work; the run's seed orders them over the slots."""
    cell = spec.cell(spec.benchmark()["workloads"][0]["name"])
    kind = spec.kind(cell.traffic["kind"])
    a, b = (kind.rows(cell.traffic, cell.config["vocab_size"], s) for s in seeds)
    assert sorted(len(r) for r in a) == sorted(cell.traffic["contexts"])
    assert [r.tobytes() for r in a] != [r.tobytes() for r in b]
    assert sorted(r.tobytes() for r in a) == sorted(r.tobytes() for r in b)
    assert len({r.tobytes() for r in a}) == len(a)
    again = kind.rows(cell.traffic, cell.config["vocab_size"], seeds[0])
    assert all((x == y).all() for x, y in zip(a, again))


# A family of the program's sparse-expert blocks at a CPU size: a dense
# layer, then an expert layer with 2 of 4 experts active per token.
TOY_FAMILY = """
import jax
from repro.models.transformer import ModelConfig, TransformerAdapter, init_params


def _mcfg(cfg):
    return ModelConfig(
        name=cfg["name"], arch_type="moe", n_layers=len(cfg["block_pattern"]),
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=tuple(cfg["block_pattern"]), n_experts=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_d_ff=cfg["moe_intermediate_size"],
        moe_capacity_factor=float(cfg["num_experts"]), tie_embeddings=True)


def model(cfg):
    return TransformerAdapter(_mcfg(cfg))


def params(cfg):
    return init_params(jax.random.PRNGKey(cfg["weights"]["seed"]), _mcfg(cfg))


def calibration_layer(cfg):
    return 1


def _layer(cfg, kind, attended):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * (2 * d * nh * hd + 2 * d * nkv * hd) + 4 * nh * hd * (attended + 1)
    if kind == "attn":
        return attn + 6 * d * cfg["intermediate_size"]
    active = cfg["num_experts_per_tok"] * cfg["moe_intermediate_size"]
    return attn + 2 * d * cfg["num_experts"] + 6 * d * active


def decode_token_flops(cfg, attended):
    return (sum(_layer(cfg, k, attended) for k in cfg["block_pattern"])
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def decode_programs(cfg, batch, context, itemsize):
    layers = cfg["block_pattern"]
    flops = batch * sum(_layer(cfg, k, context) for k in layers) // len(layers)
    return {"jit_decode_block": (flops, 1000 * itemsize)}
"""
TOY_CONFIG = {
    "name": "toy-moe", "family": "toy_moe",
    "block_pattern": ["attn", "moe_attn"], "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "torch_dtype": "float32", "calibration_tokens": 32, "weights": {"seed": 7},
    "engine": {"group_size": 4, "n_select": 8, "rank": 8, "reuse_capacity": 12,
               "kv_bits": 16, "warm_budget_bytes": 0},
}
TOY_TRAFFIC = {"kind": "context_decode", "contexts": [96, 128], "content_seed": 5,
               "turn_tokens": 32, "max_new": 256, "slots": 2, "max_seq": 388,
               "prefix_block_tokens": 16, "prefix_budget_bytes": 16 << 20}


def test_a_configuration_of_a_new_family_is_files_alone(tmp_path, monkeypatch):
    """A checkout to which a family file, a configuration, a mix, a checks
    file and entries are added serves the new cell, and both roofline
    readers count the work with the new family's numbers."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench_dir / "families" / "toy_moe.py").write_text(textwrap.dedent(TOY_FAMILY))
    (bench_dir / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    (bench_dir / "traffic" / "toy-decode.json").write_text(json.dumps(TOY_TRAFFIC))
    (bench_dir / "checks" / "toy-moe.toy-decode.json").write_text(
        json.dumps({"sample_requests": 2, "min_checked_tokens": 1}))
    bench = spec.benchmark()
    bench["configs"].append({"name": "toy-moe", "file": "bench/configs/toy-moe.json"})
    bench["workloads"].append({"name": "toy-moe.toy-decode", "config": "toy-moe",
                               "traffic": "toy-decode", "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("toy-moe.toy-decode")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))

    cell = spec.cell("toy-moe.toy-decode")
    assert {m["name"] for m in cell.per_layer} >= {"decode_mfu", "decode_block_roofline"}
    rec, *_ = run.serve_cell(cell, 3000000031, 1.0, False, None)
    t0, t1 = rec.t_open, rec.t_close
    tokens = sum(1 for r in rec.requests for t in r.token_t if t0 <= t <= t1)
    assert tokens > 0

    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    trace = {"program_s": {"jit_decode_block": 0.25}, "program_calls": {"jit_decode_block": 40}}
    rec = dataclasses.replace(rec, trace=trace, trace_bounds=(t0, t1), peak=peak)

    def layers(attended):
        """The dense layer's and the expert layer's operations for one token:
        d 64, 4 query and 2 KV heads of 16, 2 of 4 experts of 32 active."""
        attn = 2 * (2 * 64 * 64 + 2 * 64 * 32) + 4 * 64 * (attended + 1)
        return attn + 6 * 64 * 128, attn + 2 * 64 * 4 + 6 * 64 * 2 * 32

    # a token attends M*G + G/2 = 34 earlier tokens, then the tied head
    token_flops = sum(layers(34)) + 2 * 64 * 256
    mfu = spec.metric_reader("decode_mfu")(rec)
    assert mfu == pytest.approx(100.0 * tokens * token_flops / (t1 - t0) / 1e9)
    # one call of jit_decode_block: 2 rows over M*G + G = 36 gathered
    # tokens, the mean of the two layers; operations bound it
    least = 2 * sum(layers(36)) // 2 / 1e9
    assert least > 2000 / 1e9
    roofline = spec.metric_reader("decode_block_roofline")(rec)
    assert roofline == pytest.approx(100.0 * 40 * least / 0.25)
