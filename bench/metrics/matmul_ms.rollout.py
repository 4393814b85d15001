"""Device milliseconds a decode pass spends in the linears' matmuls and
their activation quantize: ops whose innermost program scope is
`matmul` or `act_quant` inside the `decode` phase of `generate`, over
the traced window's decode passes (`harness/scopes.py`)."""
from harness import scopes


def read(run):
    return scopes.per_pass_ms(run, "matmul", "act_quant")
