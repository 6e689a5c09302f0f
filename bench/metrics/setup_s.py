"""Process start to the first timed tick, compiles included."""


def read(run):
    return run.setup_s
