"""Output tokens emitted in the window over the window's length."""


def read(run):
    return run.window_tokens() / run.seconds
