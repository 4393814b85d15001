"""Device milliseconds per rollout step of the weight sync's quantize:
ops whose innermost program scope is `quantize` (`quantize_params` under
`sync_policy_weights`), over the traced window's steps
(`harness/scopes.py`).  None where the sync quantizes nothing."""
from harness import scopes


def read(run):
    r = scopes.read(run)
    if r is None:
        return None
    quantize = sum(by.get("quantize", 0.0) for by in r["scopes_s"].values())
    return 1e3 * quantize / len(run.steps) if quantize > 0 else None
