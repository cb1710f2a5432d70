"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a committed cell at a CPU size (no look
for a chip), with one fault planted where the program produces its
output, and reads ``correct`` from the result line.
"""

import json

import numpy as np
import pytest

from bench import run
from repro.core.engine import KVSwapEngine
from repro.serving import sampling

CELLS = ["qwen3-8b.longctx-decode"]


def _result(tiny, name, capsys, seed=3000000021):
    rc = run.main(["--workload", name, "--seed", str(seed), "--seconds", "2", "--trace", "0"],
                  cell=tiny(name), require_accelerator=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _stale_step(monkeypatch):
    """decode_step hands back the previous step's logits and leaves the
    engine's state where it was."""
    real = KVSwapEngine.decode_step
    last = {}

    def stale(self, token_ids):
        if id(self) not in last:
            last[id(self)] = real(self, token_ids)
        return last[id(self)]

    monkeypatch.setattr(KVSwapEngine, "decode_step", stale)


def _half_batch(monkeypatch):
    """decode_step decodes the lower half of the rows; the upper half is
    left out of the step."""
    real = KVSwapEngine.decode_step

    def half(self, token_ids):
        keep = self.row_active.copy()
        upper = np.arange(self.batch) >= self.batch // 2
        if not (keep & ~upper).any():
            return real(self, token_ids)
        self.row_active[upper] = False
        try:
            return real(self, token_ids)
        finally:
            self.row_active[:] = keep

    monkeypatch.setattr(KVSwapEngine, "decode_step", half)


def _altered_token(monkeypatch):
    """Every fifth token the greedy sampler produces is off by one."""
    real = sampling.greedy
    calls = [0]

    def altered(logits):
        out = np.asarray(real(logits))
        calls[0] += 1
        return (out + 1) % np.asarray(logits).shape[-1] if calls[0] % 5 == 0 else out

    monkeypatch.setattr(sampling, "greedy", altered)


FAULTS = {"stale_step": _stale_step, "half_batch": _half_batch, "altered_token": _altered_token}


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny, name, capsys):
    out = _result(tiny, name, capsys)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_makes_the_run_incorrect(tiny, name, fault, capsys, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _result(tiny, name, capsys)
    assert not out["correct"], out["checks"]
