"""Output tokens emitted in the window over the window's length (closed
loop; every token is served with retrieval, or the run is not correct)."""


def read(run):
    if run.loop != "closed" or run.window_s <= 0:
        return None
    return run.tokens / run.window_s
