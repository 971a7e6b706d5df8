"""Process start until jax and the program are imported and the backend is up."""


def read(run):
    return run.import_s
