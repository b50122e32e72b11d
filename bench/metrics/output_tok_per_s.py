"""Output tokens emitted in the window divided by the window's seconds."""
from bench.readers import tokens_in_window


def read(run):
    return tokens_in_window(run) / run.seconds
