"""Set-up seconds: process start to the start of the window (imports, model
and table build, weights, loading or compiling both executables, warm-up)."""


def read(run):
    return run["setup_s"]
