"""Model FLOPs of the window's RL steps (rollout prefill and decode, the
update's forward and backward, attention counted; `harness/counts.py`)
over the window's wall time times the chip's bf16 peak, in percent."""


def read(run):
    if run.kind != "rl_step" or not run.steps:
        return None
    flops = sum(s["flops"] for s in run.steps)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
