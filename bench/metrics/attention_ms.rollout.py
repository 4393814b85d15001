"""Device milliseconds a decode pass spends in attention math: ops
whose innermost program scope is `attention` (rope, qk-norm, the length
mask, scores, softmax, the PV product) inside the `decode` phase of
`generate`, over the traced window's decode passes
(`harness/scopes.py`).  A fused op counts under its consumer's scope: in
the fp8 cells the K/V dequantize, issued under `kv_read`, fuses into
attention's quantize-dequantize and reads here."""
from harness import scopes


def read(run):
    return scopes.per_pass_ms(run, "attention")
