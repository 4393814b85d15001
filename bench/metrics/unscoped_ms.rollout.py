"""Device milliseconds a decode pass spends in work that carries no
program scope: ops under no registered layer scope, in the `decode`
phase of `generate` or in no phase, over the traced window's decode
passes (`harness/scopes.py`).  They are XLA's own: the layer scan's
slices and updates of each layer's state, and the loop's carry and
layout copies, which land in no phase when no named op of their
computation gives them one (the whole KV stack's carry copies, mistral's
f32 copies of dequantized weights)."""
from harness import scopes


def read(run):
    r = scopes.read(run)
    if r is None or not r["decode_passes"]:
        return None
    unscoped = sum(r["scopes_s"].get(phase, {}).get(scopes.UNSCOPED, 0.0)
                   for phase in ("decode", scopes.OTHER))
    return 1e3 * unscoped / r["decode_passes"]
