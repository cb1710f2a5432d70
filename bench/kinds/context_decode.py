"""Closed loop over long built contexts.

The mix's ``contexts`` lists each slot's context length.  Set-up builds
every context as turns of ``turn_tokens`` through the prefix cache, like a
transcript arriving in segments: each turn asks for one token, the first
turn is a cold admission and each later one restores the turn before it
and prefills the new segment.  The last turn asks for ``max_new`` tokens,
and in the window every slot decodes.  The contexts' token ids are drawn
from the mix's own ``content_seed``, so every run builds and decodes the
same contexts and does the same work: with random ids the share of KV
groups the reuse buffer serves, and so the bytes fetched per token, move
with the ids.  The run's seed orders the contexts over the slots.
"""

from __future__ import annotations

import time

import numpy as np

from bench.serve import Client, CompileCounter, Tracer, Window


def rows(traffic: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """One token-id context per slot: the mix's contexts, in the order the
    seed draws."""
    rng = np.random.default_rng(int(traffic["content_seed"]))
    lengths = rng.permutation(np.asarray(traffic["contexts"], np.int64))
    ctxs = [rng.integers(0, vocab, int(n)).astype(np.int64) for n in lengths]
    order = np.random.default_rng(int(seed)).permutation(len(ctxs))
    return [ctxs[i] for i in order]


def build_contexts(drv: Client, ctxs: list[np.ndarray], turn: int, max_new: int) -> list:
    """Admit each row's context as turns of ``turn`` tokens, then submit
    the full contexts with ``max_new`` to decode.  Each final request keeps
    the tokens its turns were served, with the position that chose each."""
    longest = max(len(r) for r in ctxs)
    turns: list[list] = [[] for _ in ctxs]
    for end in range(turn, longest, turn):
        for i, r in enumerate(ctxs):
            if end <= len(r) - turn:
                turns[i].append(drv.submit(r[:end], 1))
        drv.drain()
    finals = [drv.submit(r, max_new) for r in ctxs]
    for req, ts in zip(finals, turns):
        req.earlier = [(len(t.prompt) - 1, t.tokens[0]) for t in ts if t.tokens]
        req.failed = next((t.failed for t in ts if t.failed), None)
    return finals


def warm_decode(drv: Client, compiles: CompileCounter, group: int) -> None:
    """Decode until ``2 * group`` steps in a row built no program (a group
    of ``group`` tokens completes every ``group`` steps), at least
    ``3 * group`` steps."""
    quiet, steps = 0, 0
    while steps < 3 * group or quiet < 2 * group:
        n = compiles.count
        drv.step()
        steps += 1
        quiet = quiet + 1 if compiles.count == n else 0
        if steps > 40 * group:
            break


def window(drv: Client, compiles: CompileCounter, cell, seed: int, seconds: float,
           tracer: Tracer) -> Window:
    traffic, eng = cell.traffic, cell.engine
    ctxs = rows(traffic, cell.config["vocab_size"], seed)
    finals = build_contexts(drv, ctxs, traffic["turn_tokens"], traffic["max_new"])
    warm_decode(drv, compiles, eng["group_size"])
    before = drv.snapshot(compiles)
    t_open = time.perf_counter()
    tracer.t_start += t_open
    tracer.t_stop += t_open
    now = t_open
    while now < t_open + seconds:
        tracer.tick(now)
        now = drv.step()
    tracer.stop()
    return Window(before=before, t_open=t_open, t_close=now, attempted=finals,
                  served=finals, requests=finals)
