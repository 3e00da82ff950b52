"""Process start to the window's start: weights, programs (compiled or from
the cache), warm-up."""


def read(run):
    return run.setup_s
