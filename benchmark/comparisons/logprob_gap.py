"""The comparison ``logprob_gap``: answers with their chosen tokens'
log-probabilities, matched by ``id`` to the pool, against the configuration's
plain reference, teacher-forced.

For a seeded sample of the rows the window delivered (``compare.sample_rows``
of them, the longest document among them) the reference runs its full forward
(float32, no cache, no chunks) over the prompt's tokens followed by the tokens
the program chose, after the program's state is freed. Logits decide, not
sampled text. Numbers compared (each printed beside its limit):

    logprob_gap             the largest |program's log-probability - reference's|
                            over every token of the sampled answers. Limit: the
                            configuration's ``compare.logprob_gap_max``.
    greedy_regret           the largest (reference's max logit - reference's logit
                            of the chosen token): the program chose the reference's
                            argmax or a near tie. Limit: ``compare.greedy_regret_max``.
    answers_not_64_tokens   delivered rows of the window whose answer is not
                            ``max_new_tokens`` tokens and as many finite
                            log-probabilities. Limit 0.
    token_ids_outside_slice chosen ids of the window outside the held slice of the
                            vocabulary. Limit 0.
    ids_out_of_sequence     rows of the whole stream whose id is not its
                            predecessor's + 1 (mod the pool). Limit 0.

The prompt's tokens are the program's tokenizer's (host code outside the timed
device path: one hashed id a word): what is held to the reference is the model,
and ``prompt_tokens_not_words`` (limit 0) counts sampled prompts whose token
count is not their word count.

With ``control`` the same numbers with the control in the program's place: the
reference with both operands of every matrix product rounded to float8_e4m3
(its log-probabilities of the same tokens, and the tokens it would have chosen,
teacher-forced on the same prefix). It has to read not correct.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from lib.compare import out_of_sequence


def _rows(window_parts):
    """-> ids (n,), offsets (n + 1,), tokens, logprobs over the window's partitions."""
    ids = np.concatenate([i for i, _ in window_parts])
    sizes = np.concatenate([np.diff(a[0]) for _, a in window_parts])
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    return (ids, offsets, np.concatenate([a[1] for _, a in window_parts]),
            np.concatenate([a[2] for _, a in window_parts]))


def _sample(ids: np.ndarray, pool, k: int, seed: int) -> np.ndarray:
    """``k`` of the pool rows delivered in the window, the longest document among them."""
    delivered = np.unique(ids)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    longest = delivered[int(np.argmax([len(pool[i]) for i in delivered]))]
    others = delivered[delivered != longest]
    return np.sort(np.append(rng.choice(others, size=min(k - 1, len(others)), replace=False), longest))


def _log_softmax(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    m = x.max(-1, keepdims=True)
    return x - m - np.log(np.exp(x - m).sum(-1, keepdims=True))


def compare(cell, seed: int, pool, id_stream: List[np.ndarray],
            window_parts: List[Tuple[np.ndarray, tuple]], control: bool = False) -> dict:
    from daft_tpu.utils.tokenizer import HashingTokenizer

    cfg, ref = cell.config, cell.reference
    rules, options = cfg["compare"], cfg["options"]
    new, vocab = options["max_new_tokens"], cfg["vocab_size"]
    ids, offsets, tokens, logprobs = _rows(window_parts)
    sizes = np.diff(offsets)
    finite = np.add.reduceat(np.isfinite(logprobs).astype(np.int64), offsets[:-1]) if len(tokens) else sizes
    wrong_length = int(np.count_nonzero((sizes != new) | (finite != sizes)))
    outside = int(np.count_nonzero((tokens < 0) | (tokens >= vocab)))

    chosen = _sample(ids, pool, rules["sample_rows"], seed)
    first = {int(i): int(np.flatnonzero(ids == i)[0]) for i in chosen}  # its first delivery in the window
    tok = HashingTokenizer(vocab, options["max_prompt_tokens"])
    prompt_tokens, lengths = tok.encode_batch([pool[i] for i in chosen])
    not_words = int(sum(n != min(len(pool[i].split()), options["max_prompt_tokens"]) for i, n in zip(chosen, lengths)))
    sequences, starts, answers, served = [], [], [], []
    for k, i in enumerate(chosen):
        a, b = offsets[first[int(i)]], offsets[first[int(i)] + 1]
        answers.append(tokens[a:b])
        served.append(logprobs[a:b])
        sequences.append(np.concatenate([prompt_tokens[k, :lengths[k]], tokens[a:b]]).astype(np.int32))
        starts.append(int(lengths[k]) - 1)
    got = ref.forward_many(cfg, seed, sequences, precisions=("f32", "fp8") if control else ("f32",),
                           logits_from=starts, pad_to=options["max_prompt_tokens"] + new)

    def numbers(lp_served, chose) -> Dict[str, Dict[str, float]]:
        gap = regret = 0.0
        for logits, lp, ans, mine in zip(got["f32"], lp_served, answers, chose):
            at = np.arange(len(ans))
            logits = logits[:len(ans)]  # the last position predicts a token nobody asked for
            gap = max(gap, float(np.max(np.abs(_log_softmax(logits)[at, ans] - lp), initial=0.0)))
            regret = max(regret, float(np.max(logits.max(-1) - logits[at, mine], initial=0.0)))
        return {
            "logprob_gap": {"value": gap, "limit": rules["logprob_gap_max"]},
            "greedy_regret": {"value": regret, "limit": rules["greedy_regret_max"]},
            f"answers_not_{new}_tokens": {"value": wrong_length, "limit": 0},
            "token_ids_outside_slice": {"value": outside, "limit": 0},
            "ids_out_of_sequence": {"value": out_of_sequence(id_stream, len(pool)), "limit": 0},
            "prompt_tokens_not_words": {"value": not_words, "limit": 0},
            "rows_compared": {"value": int(len(chosen)), "limit": None},
            "tokens_compared": {"value": int(sum(len(a) for a in answers)), "limit": None},
        }

    out = {"numbers": numbers(served, answers), "failed": wrong_length}
    if control:  # the reference in fp8 answers for the sampled rows in the program's place
        low = [l[:len(a)] for l, a in zip(got["fp8"], answers)]
        out["control"] = numbers([_log_softmax(l)[np.arange(len(a)), a] for l, a in zip(low, answers)],
                                 [l.argmax(-1) for l in low])
    return out
