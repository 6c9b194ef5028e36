"""Process start to the first slot boundary of the window."""


def read(run):
    return run.setup_s
