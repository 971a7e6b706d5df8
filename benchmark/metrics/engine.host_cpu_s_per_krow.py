"""Process CPU time (user + system, all threads) over the window, per thousand rows."""


def read(run):
    return 1000.0 * run.cpu_s / run.window.rows
