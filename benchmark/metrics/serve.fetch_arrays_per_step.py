"""Arrays one ``serve.fetch`` brings to the host (``arrays``: the tokens, their log-probabilities and the model's counts of the step), over the window's decode steps: 5 / 7 / 2 in the three cells; a loop that fetches one packed array reads 1, beside ``serve.idle_ms_per_step``."""

from lib import idle_by_span


def read(run):
    got = idle_by_span.read(run)
    return got["fetch_arrays"] / got["steps"] if got and got["steps"] else None
