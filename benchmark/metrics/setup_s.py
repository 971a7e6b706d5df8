"""Process start to the opening of the window: imports, data, instantiate, compile or cache load, warm-up partitions."""


def read(run):
    return run.setup_s
