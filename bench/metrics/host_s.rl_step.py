"""Host work of an RL step between its phases (prompts, rewards,
advantages, packing): the trainer's `step_s` less its sync, rollout and
update, averaged over the window."""


def read(run):
    if run.kind != "rl_step" or not run.steps:
        return None
    rest = [s["step_s"] - s["sync_ms"] / 1e3 - s["rollout_s"] - s["update_s"]
            for s in run.steps]
    return sum(rest) / len(rest)
