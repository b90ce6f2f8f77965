"""Set-up seconds: process start to the first query of the window."""


def read(run):
    return run.setup_s
