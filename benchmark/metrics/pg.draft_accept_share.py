"""Of the drafts the window's speculative steps made (``drafted``, one an active slot), the share accepted: sum(``accepted``) / sum(``drafted``) over the window's ``serve.decode_step`` spans. A reading of the weights and the exact acceptance rule, not a target: a drafting block drawn at random is accepted ~45-50% of the time under two independent softmaxes of logits spread ~1, a trained one ~85-90%. No change to the program can move it without changing what is emitted."""

from lib import decoder_scopes


def read(run):
    n = decoder_scopes.counters(run, "serve.decode_step", "accepted", "drafted")
    return None if n is None or not n["drafted"] else 100.0 * n["accepted"] / n["drafted"]
