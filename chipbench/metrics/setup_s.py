"""Process start to the first step of the window: imports, the net, the
compiled step (or its read from the cache), weights and batches from the
seed, the first steps that are compared later, warm-up.  The reference
runs after the window and is not part of it."""


def read(run):
    return run["setup_s"]
