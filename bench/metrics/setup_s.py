"""Set-up time: process start to the first measured step (host clock)."""


def read(run):
    return run.setup_s
