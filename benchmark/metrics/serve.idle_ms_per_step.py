"""Device idle milliseconds a decode step of the traced window under ``serve.decode_step``, its two children included (one span is one turn of the batcher's loop): what a loop that dispatches step i + 1 before it fetches step i could give back."""

from lib import idle_by_span


def read(run):
    return idle_by_span.step_idle_ms(run)
