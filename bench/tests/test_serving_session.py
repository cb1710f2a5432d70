"""The serving-session layer: the reader of ``session_host_ms.decode`` on
synthetic spans, and a run whose batched greedy sampling is broken
underneath coming out not correct."""

import json
import types

import pytest

from bench import run, spec
from repro.serving import api


def _run(spans, t_open, t_close):
    return types.SimpleNamespace(decode=types.SimpleNamespace(spans=spans),
                                 t_open=t_open, t_close=t_close)


# decode_step spans (start, end) on the host clock, in seconds
SPANS = [(0.0, 1.0), (1.5, 2.5), (2.75, 3.75), (4.75, 5.75)]


@pytest.mark.parametrize("window, ms", [
    # every pair inside: gaps 0.5, 0.25 and 1.0 s
    ((0.0, 6.0), 1e3 * (0.5 + 0.25 + 1.0) / 3),
    # the pair from 1.0 to 1.5 straddles t_open and is left out
    ((1.2, 6.0), 1e3 * (0.25 + 1.0) / 2),
    # the last pair ends past t_close
    ((0.0, 4.0), 1e3 * (0.5 + 0.25) / 2),
    # no pair lies in the window
    ((1.2, 3.0), None),
])
def test_session_host_ms_reads_gaps_between_decode_steps_in_the_window(window, ms):
    read = spec.metric_reader("session_host_ms.decode")
    got = read(_run(SPANS, *window))
    assert got == (None if ms is None else pytest.approx(ms))


def test_session_host_ms_reads_nothing_without_pairs():
    read = spec.metric_reader("session_host_ms.decode")
    assert read(_run([], 0.0, 10.0)) is None
    assert read(_run([(1.0, 2.0)], 0.0, 10.0)) is None


def test_altered_batched_ids_make_the_run_incorrect(tiny, capsys, monkeypatch):
    """Every fifth decode step the batched argmax hands back each row's id
    off by one."""
    real = api.greedy_rows
    calls = [0]

    def altered(logits):
        ids = real(logits)
        calls[0] += 1
        return (ids + 1) % logits.shape[-1] if calls[0] % 5 == 0 else ids

    monkeypatch.setattr(api, "greedy_rows", altered)
    name = "qwen3-8b.longctx-decode"
    rc = run.main(["--workload", name, "--seed", "3000000021", "--seconds", "2", "--trace", "0"],
                  cell=tiny(name), require_accelerator=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert calls[0] > 5
    assert not out["correct"], out["checks"]
