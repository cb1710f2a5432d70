"""Process start to window open: weights, compile-cache loads, the
contexts or warm-up traffic the cell needs."""


def read(run):
    return run.setup_s
