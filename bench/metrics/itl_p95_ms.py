"""95th percentile of every inter-token gap in the window, between the
step() returns that delivered consecutive tokens of a request."""

from bench.stats import inter_token_gaps, percentile


def read(run):
    gaps = inter_token_gaps(run.requests, run.t_open, run.t_close)
    return 1e3 * percentile(gaps, 95) if gaps else None
