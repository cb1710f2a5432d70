"""Prediction: the share of each step's selected groups that the step
before also selected, per layer and row, over the window (the engine's
StepStats.pred_shared_groups over pred_prev_groups)."""


def read(run):
    prev = run.after["select_prev"] - run.before["select_prev"]
    shared = run.after["select_shared"] - run.before["select_shared"]
    return 100.0 * shared / prev if prev else None
