"""Host-clock milliseconds of `sync_policy_weights` (which blocks on its
result) per rollout step, averaged over the window."""


def read(run):
    if run.kind != "rollout" or not run.steps:
        return None
    return sum(s["sync_ms"] for s in run.steps) / len(run.steps)
