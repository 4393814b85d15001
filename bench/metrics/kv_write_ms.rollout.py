"""Device milliseconds a decode pass spends writing the KV cache: ops
whose innermost program scope is `kv_write` (quantizing the fresh K/V,
the scatter into the pool) inside the `decode` phase of `generate`, over
the traced window's decode passes (`harness/scopes.py`)."""
from harness import scopes


def read(run):
    return scopes.per_pass_ms(run, "kv_write")
