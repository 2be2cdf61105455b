"""setup_s (s): process start to the window opening: imports, weights,
compile or cache load, warm-up."""


def read(run):
    return run.setup_s
