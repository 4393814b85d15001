"""Seconds per RL step: the window's wall time over the whole
`train_step` calls in it (host clock)."""


def read(run):
    if run.kind != "rl_step" or not run.steps:
        return None
    return run.window_s / len(run.steps)
