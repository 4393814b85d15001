"""Device milliseconds a decode pass spends reading the KV cache: ops
whose innermost program scope is `kv_read` (the page-table gather, the
K/V dequantize, the attention kernels when they run) inside the `decode`
phase of `generate`, over the traced window's decode passes
(`harness/scopes.py`).  A fused op counts under its consumer's scope: in
the fp8 cells XLA fuses the K/V dequantize into attention's
quantize-dequantize, so it reads as `attention_ms`, and this metric holds
the gather alone."""
from harness import scopes


def read(run):
    return scopes.per_pass_ms(run, "kv_read")
