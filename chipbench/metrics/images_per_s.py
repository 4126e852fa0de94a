"""Images answered in the window over the window's length (host clock)."""


def read(run):
    return run.images / run.window_s if run.images else None
