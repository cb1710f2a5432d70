"""``chip_smoke.py``: the CPU rehearsal runs every phase, and without a TPU
the full-size run refuses to start and prints no result."""

from __future__ import annotations

import json
import os
import sys

import jax

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _chip_smoke():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import chip_smoke

    return chip_smoke


def test_tiny_runs_every_phase_and_names_the_device(capsys):
    assert _chip_smoke().main(["--tiny"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    dev = jax.devices()[0]
    assert last == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    assert last["device"]["count"] >= 1
    for phase in ("a:reference", "b:served", "c:pallas"):
        assert any(line.startswith(f"[{phase}] PASS") for line in lines), phase
    assert not any("FAIL" in line for line in lines)


def test_full_size_needs_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"   # conftest pins the CPU
    assert _chip_smoke().main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
