"""Every token emitted inside the window, over the window's seconds."""


def read(run):
    return run.window.tokens_in_window / run.seconds
