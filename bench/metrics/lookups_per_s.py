"""Keys answered in the window over the window's measured length."""


def read(run):
    return run.lookups / run.window_s
