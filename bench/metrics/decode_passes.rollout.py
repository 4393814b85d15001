"""Decode passes per rollout step: executions of `generate`'s decode
loop body in the traced window (the most executions of any one device
instruction scoped `sample` in the `decode` phase; `harness/scopes.py`),
over the window's steps."""
from harness import scopes


def read(run):
    r = scopes.read(run)
    if r is None or not r["decode_passes"]:
        return None
    return r["decode_passes"] / len(run.steps)
