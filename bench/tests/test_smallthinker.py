"""The SmallThinker cell at a CPU size: the served path runs through
``run.serve_cell`` and is correct, the float8 control and every planted
fault are not, and both roofline readers count with the family's numbers.

The cell keeps its layer pattern (published layers 1-4: three windowed,
one full), its experts' routing (router before attention, ReGLU, top-k
renormalised) and its engine paths, with every width and length cut down:
a 64-token window under contexts of 4-6 windows.
"""

import copy
import dataclasses
import json

import pytest

from bench import compare, run, spec
from bench.tests.test_faults import FAULTS

NAME = "smallthinker-21b-a3b.longctx-swa-decode"
SEED = 3000000077
TINY_CONFIG = {
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "moe_ffn_hidden_size": 64, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 2, "vocab_size": 512, "sliding_window_size": 64,
    "calibration_tokens": 64,
    "engine": {"group_size": 4, "n_select": 16, "rank": 16, "reuse_capacity": 24,
               "kv_bits": 16, "warm_budget_bytes": 0},
}
TINY_WEIGHTS = {"qk_read_dims": 64, "qk_rope_pairs": 4, "head_agreement": 0.97}
TINY_TRAFFIC = {"contexts": [256, 320, 384, 384], "turn_tokens": 64, "max_new": 512,
                "slots": 4, "max_seq": 1028, "prefix_budget_bytes": 64 << 20}
# From CPU readings of four seeds (3000000077, 3000000021, 11, 12): sound
# runs read gap_mean 0.084-0.102, the float8 control 1.62-1.78.
TINY_CHECKS = {"sample_requests": 4, "min_checked_tokens": 10, "gap_mean": {"limit": 0.5}}


def tiny_cell() -> spec.Cell:
    cell = spec.cell(NAME)
    cfg = copy.deepcopy(cell.config)
    cfg.update(TINY_CONFIG)
    cfg["weights"] = {**cfg["weights"], **TINY_WEIGHTS}
    return spec.Cell(name=NAME, config=cfg, traffic={**cell.traffic, **TINY_TRAFFIC}, chips=1,
                     end_to_end=cell.end_to_end, per_layer=cell.per_layer,
                     checks={**cell.checks, **TINY_CHECKS})


def _result(capsys, seed=3000000021):
    argv = ["--workload", NAME, "--seed", str(seed), "--seconds", "2", "--trace", "0"]
    assert run.main(argv, cell=tiny_cell(), require_accelerator=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    out = _result(capsys)
    assert out["correct"], out["checks"]


def test_control_fails_where_the_program_passes():
    cell = tiny_cell()
    _, params, served, _, failed, _ = run.serve_cell(cell, SEED, 2.0, False, None)
    ref = spec.reference(cell.config["reference"])
    sample = compare.sample(served, cell.checks["sample_requests"], SEED)
    n_tokens = sum(len(r.tokens) for r in sample)
    program = compare.numbers(compare.served_gaps(ref, params, cell.config, sample))
    control = compare.numbers(compare.control_gaps(ref, params, cell.config, sample))
    ok, checks = compare.judge(program, failed, n_tokens, cell.checks)
    assert ok, checks
    ok, checks = compare.judge(control, failed, n_tokens, cell.checks)
    assert not ok, checks


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, capsys, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _result(capsys)
    assert not out["correct"], out["checks"]


def test_rooflines_count_with_the_family():
    """On a recorded run: ``decode_block_roofline`` over both decode
    programs, ``window_block_roofline`` over the windowed one alone, each
    call at its least time from the family's operations and bytes."""
    cell = spec.cell(NAME)
    cfg = cell.config
    family = spec.family(cfg["family"])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"program_s": {"jit_decode_block": 0.004, "jit_decode_window_block": 0.012,
                           "jit_other": 1.0},
             "program_calls": {"jit_decode_block": 4, "jit_decode_window_block": 12,
                               "jit_other": 9}}
    rec = run.Run(cell=NAME, config=cfg, engine=cell.engine, traffic=cell.traffic,
                  setup_s=0.0, t_open=0.0, t_close=1.0, requests=[], decode=None,
                  before={}, after={}, io_wait_s=0.0, trace=trace, trace_bounds=(0.0, 1.0),
                  peak=peak)
    # 8 rows over the full layer's M·G + G = 404 gathered tokens; the
    # window program over the 4096-token ring; 6 of 64 experts a token
    full, window = family.decode_call(cfg, 8, 405, 2), family.decode_call(cfg, 8, 4096, 2)
    assert family.decode_programs(cfg, 8, 404, 2) == {
        "jit_decode_block": full, "jit_decode_window_block": window}
    routed = 64 * (1 - (1 - 6 / 64) ** 8)
    assert 34.8 < routed < 35.0
    expert_bytes = routed * 3 * 2560 * 768 * 2
    assert window[1] == pytest.approx(expert_bytes + 2 * 8 * 4095 * 512 * 2
                                      + (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
                                         + 2 * 2560) * 2 + 2 * 8 * 2560 * 2 + 2 * 8 * 512 * 2)
    least = {p: spec.roofline_seconds(f, b, peak)[0] for p, (f, b) in
             (("jit_decode_block", full), ("jit_decode_window_block", window))}
    both = 100.0 * (4 * least["jit_decode_block"] + 12 * least["jit_decode_window_block"]) / 0.016
    assert spec.metric_reader("decode_block_roofline")(rec) == pytest.approx(both)
    alone = 100.0 * 12 * least["jit_decode_window_block"] / 0.012
    assert spec.metric_reader("window_block_roofline")(rec) == pytest.approx(alone)
    assert 0.0 < alone <= 100.0
    # a trace without the windowed program reads nothing
    trace = {k: {"jit_decode_block": v["jit_decode_block"]} for k, v in trace.items()}
    assert spec.metric_reader("window_block_roofline")(
        dataclasses.replace(rec, trace=trace)) is None
