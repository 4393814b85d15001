"""Device milliseconds a decode pass spends dequantizing fp8 weight
blocks: ops whose innermost program scope is `weight_dequant` inside the
`decode` phase of `generate`, over the traced window's decode passes
(`harness/scopes.py`).  None where the linears are not quantized."""
from harness import scopes


def read(run):
    return scopes.per_pass_ms(run, "weight_dequant")
