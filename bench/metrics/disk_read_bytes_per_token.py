"""Tiers: bytes the disk tier served in the window (IOAccountant
read_bytes) per token emitted in the window."""

from bench.stats import tokens_in


def read(run):
    tokens = tokens_in(run.requests, run.t_open, run.t_close)
    return (run.after["read_bytes"] - run.before["read_bytes"]) / tokens if tokens else None
