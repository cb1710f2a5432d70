"""Output tokens stamped in the window over the window's length."""

from bench.stats import tokens_in


def read(run):
    return tokens_in(run.requests, run.t_open, run.t_close) / (run.t_close - run.t_open)
