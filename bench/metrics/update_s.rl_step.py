"""The trainer's own `update_s` of each RL step, ended by
`block_until_ready` inside `train_step`, averaged over the window."""


def read(run):
    if run.kind != "rl_step" or not run.steps:
        return None
    return sum(s["update_s"] for s in run.steps) / len(run.steps)
