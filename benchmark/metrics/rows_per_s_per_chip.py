"""Rows of every partition that arrived in the window, over the window, per device the parameters occupy."""


def read(run):
    return run.window.rows_per_s / run.n_devices
