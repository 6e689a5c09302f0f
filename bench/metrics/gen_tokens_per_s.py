"""Generated tokens emitted in the window over the window's seconds."""
from bench import window


def read(run):
    return window.gen_tokens_per_s(run.ticks)
