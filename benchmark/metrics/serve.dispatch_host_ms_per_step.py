"""The host's milliseconds from a decode step's start to the start of its ``serve.fetch`` (key split and the call of the decode program), median over the window's steps: what a loop that dispatches ahead has to fit beside a step of ``*decode_step_ms``."""

from lib import idle_by_span


def read(run):
    return idle_by_span.dispatch_host_ms(run)
