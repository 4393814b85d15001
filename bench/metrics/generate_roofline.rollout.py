"""The least time of the window's `generate` calls, from their shapes
(`harness/counts.py`: per forward pass the larger of bytes over HBM
bandwidth and FLOPs over the bf16 peak), over the device time of the
`generate` executable in the trace, in percent."""


def read(run):
    if run.kind != "rollout" or not run.trace:
        return None
    device = sum(v for k, v in run.trace["executables_s"].items()
                 if "generate" in k)
    if device <= 0:
        return None
    least = sum(s["least_s"] for s in run.steps)
    return 100.0 * least / device
