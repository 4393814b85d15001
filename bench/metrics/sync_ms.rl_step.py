"""The trainer's own `sync_ms` of each RL step, ended by
`block_until_ready` inside `train_step`, averaged over the window."""


def read(run):
    if run.kind != "rl_step" or not run.steps:
        return None
    return sum(s["sync_ms"] for s in run.steps) / len(run.steps)
