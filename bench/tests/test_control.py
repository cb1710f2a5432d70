"""The control must come out not correct.

The control is the plain reference put in the program's place, computed
in float8: at a CPU size, for each committed cell, the tokens it ranks
first fail the cell's limits where the program's served tokens pass them.
"""

import pytest

from bench import compare, run, spec

CELLS = ["qwen3-8b.longctx-decode"]
SEED = 3000000077


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(tiny, name):
    cell = tiny(name)
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
