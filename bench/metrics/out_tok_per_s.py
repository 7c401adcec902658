"""Output tokens the harness saw in the window, over the window's seconds."""


def read(run):
    return len(run.tokens_in_window()) / run.seconds
