"""Programs compiled or loaded from the compile cache between the window's opening and its close (should read 0)."""


def read(run):
    return float(run.compiles_in_window)
