"""The longest single gap between partition arrivals, as a share of the window."""


def read(run):
    return 100.0 * run.window.longest_gap_s / run.window.seconds
