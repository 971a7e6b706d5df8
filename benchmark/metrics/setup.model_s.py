"""Query start to the first partition: parameter init, executable load or compile, first forward."""


def read(run):
    return run.model_s
