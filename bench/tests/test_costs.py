import types

import pytest

from bench import spec

DENSE = spec.family("dense_gqa")


def test_decode_block_counts_at_qwen3_8b_widths():
    cfg = spec.config("qwen3-8b")
    # d 4096, 32 query and 8 KV heads of 128, d_ff 12288
    matmul = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12288
    assert DENSE.layer_matmul_params(cfg) == matmul == 192_937_984
    assert DENSE.layer_params(cfg) == matmul + 2 * 4096 + 2 * 128
    b, ctx = 8, 404
    flops = 2 * b * matmul + 4 * b * 32 * 128 * (ctx + 1)
    assert DENSE.decode_block_flops(cfg, b, ctx) == flops
    kv = 2 * b * ctx * 8 * 128 * 4
    act = 2 * b * 4096 * 4 + 2 * b * 1024 * 4
    nbytes = DENSE.layer_params(cfg) * 4 + kv + act
    assert DENSE.decode_block_bytes(cfg, b, ctx, 4) == nbytes
    assert DENSE.decode_programs(cfg, b, ctx, 4) == {"jit_decode_block": (flops, nbytes)}


def test_decode_token_flops_adds_the_head():
    cfg = spec.config("qwen3-8b")
    per_layer = 2 * DENSE.layer_matmul_params(cfg) + 4 * 32 * 128 * 401
    assert DENSE.decode_token_flops(cfg, 400) == 4 * per_layer + 2 * 4096 * 151936


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert spec.roofline_seconds(100.0, 50.0, peak) == (5.0, "memory")
    assert spec.roofline_seconds(1000.0, 5.0, peak) == (10.0, "compute")


def test_roofline_reader_sums_the_programs_a_family_names(monkeypatch):
    """Calls times least time of each program the family names, summed,
    over the summed device time of those programs; a program that is not
    named, or not in the trace, adds nothing."""
    programs = {"jit_a": (2e9, 1e6), "jit_b": (1e6, 3e6), "jit_unrun": (1.0, 1.0)}
    family = types.SimpleNamespace(decode_programs=lambda cfg, batch, context, itemsize: programs)
    monkeypatch.setattr(spec, "family", lambda name: family)
    run = types.SimpleNamespace(
        engine={"n_select": 1, "group_size": 1}, traffic={"slots": 1},
        config={"family": "stub", "torch_dtype": "bfloat16"},
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        trace={"program_s": {"jit_a": 0.5, "jit_b": 0.25, "jit_other": 9.0},
               "program_calls": {"jit_a": 100, "jit_b": 20, "jit_other": 1}})
    read = spec.metric_reader("decode_block_roofline")
    # jit_a: operations bound it (2 ms against 1 ms); jit_b: bytes (3 ms)
    assert read(run) == pytest.approx(100.0 * (100 * 2e-3 + 20 * 3e-3) / 0.75)
    run.trace = {"program_s": {"jit_other": 1.0}, "program_calls": {"jit_other": 1}}
    assert read(run) is None
