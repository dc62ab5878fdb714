"""Set-up: process start to the window's opening, compiles included."""


def read(run):
    return run.setup_s
