"""Output tokens the clients received inside the window, over the
window's seconds."""


def read(run):
    return run.tokens_in_window() / run.window_s
