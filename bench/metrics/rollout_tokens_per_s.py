"""Generated response tokens (the sum of `response_mask`) of every whole
rollout step in the window, over the window's wall time (host clock)."""


def read(run):
    if run.kind != "rollout" or not run.steps:
        return None
    return sum(s["tokens"] for s in run.steps) / run.window_s
