"""The comparison ``spec_logprob_gap``: sampled answers of a model that drafts its
own tokens, with each emitted token's log-probability and each draft's (where it
stood in the answer, its id, its log-probability under the drafting block),
matched by ``id`` to the pool, against the configuration's plain reference,
teacher-forced over the prompt and the emitted tokens.

For a seeded sample of the rows the window delivered (``compare.sample_rows`` of
them, the longest document among them; ``logprob_gap``'s sample and row handling,
loaded from that file) the reference runs ``forward_many`` after the program's
state is freed: the main model's logits at every answer position and the drafting
block's at every position before. Numbers compared (each printed beside its limit,
where it has one):

    logprob_gap              the largest |program's log p - reference's| over every emitted token of the sampled
                             answers. Limit ``compare.logprob_gap_max``.
    draft_logprob_gap        the largest |program's log q(d) - the reference drafting block's| over every draft of the
                             sampled rows (a draft at answer index j is the block's reading at the position before
                             the token at j - 1, from the main model's hidden state there and that token).
                             Limit ``compare.draft_logprob_gap_max``.
    sampled_nll_excess       |the mean over the sampled answers' tokens of -log p_ref(token) - H(p_ref)|, p_ref the
                             reference's distribution at the configuration's temperature: ~0 for tokens drawn from the
                             model's own p (its noise ~ 1 / sqrt(tokens)), ~+0.5 for a sampler that accepts every draft
                             of a drafting block accepted half the time, ~-3 (read 3) for greedy tokens. Limit
                             ``compare.sampled_nll_excess_max``; ``sampled_nll_excess_signed`` is the mean itself.
    corrections_not_from_residual
                             the sampled rows' rejected drafts (the token at a draft's place is not the draft) whose
                             emitted correction c has log p_ref(c) below log q_ref(c) by more than the two gaps' limits
                             together (p and q at the configuration's temperature): a correction is drawn from
                             max(0, p - q), so the program's own p(c) exceeds its q(c), and its p and q are within those
                             limits of the reference's. A correction drawn from p instead lands there a sixth of the
                             time or more. Limit 0.
    answers_not_<n>_tokens   delivered rows of the window whose answer is not ``max_new_tokens`` tokens with as many
                             finite log-probabilities, or whose drafts are not in order inside it (positions strictly
                             increasing in [1, n), finite log-probabilities). Limit 0.
    token_ids_outside_slice  emitted and drafted ids of the window outside the held slice of the vocabulary. Limit 0.
    ids_out_of_sequence      rows of the whole stream whose id is not its predecessor's + 1 (mod the pool). Limit 0.
    prompt_tokens_not_words  sampled prompts whose token count is not their word count. Limit 0.

``greedy_regret`` does not apply to sampled tokens and is not computed. Reported
with no limit: ``draft_accept_share``, the share of the sampled rows' drafts that
stand in their answer (a reading of the weights and the acceptance rule).

With ``control`` the same numbers with the control in the program's place: the
reference with both operands of every matrix product rounded to float8_e4m3, its
log-probabilities of the same tokens and drafts, and tokens drawn from its own
distributions, teacher-forced on the same prefixes. It has to read not correct.
A sampler of any precision draws from its own p, whose distance to the float32
reference's is a fraction of ``sampled_nll_excess``'s noise: the control fails by
the two gaps, not by it.
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

from lib import manifest
from lib.compare import out_of_sequence

_gap = manifest.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "logprob_gap.py"))
_sample, _log_softmax = _gap._sample, _gap._log_softmax


def _rows(window_parts):
    """-> ids (n,), offsets (n + 1,), tokens, logprobs, draft offsets (n + 1,), draft positions, ids, logprobs."""
    ids = np.concatenate([i for i, _ in window_parts])

    def flat(k):
        return np.concatenate([a[k] for _, a in window_parts])

    def offsets(k):
        return np.concatenate([[0], np.cumsum(np.concatenate([np.diff(a[k]) for _, a in window_parts]))])

    return ids, offsets(0), flat(1), flat(2), offsets(3), flat(4), flat(5), flat(6)


def drafts_in_order(positions: np.ndarray, logprobs: np.ndarray, n: int) -> bool:
    """A row's drafts stand at strictly increasing places of its answer, each after its first token and inside it,
    with finite log-probabilities."""
    return bool(np.all(np.diff(positions) > 0) and np.all((positions >= 1) & (positions < n)) and np.all(np.isfinite(logprobs)))


def _tempered(logits: np.ndarray, temperature: float) -> np.ndarray:
    return _log_softmax(logits / max(temperature, 1e-6))


def compare(cell, seed: int, pool, id_stream: List[np.ndarray],
            window_parts: List[Tuple[np.ndarray, tuple]], control: bool = False) -> dict:
    from daft_tpu.utils.tokenizer import HashingTokenizer

    cfg, ref = cell.config, cell.reference
    rules, options = cfg["compare"], cfg["options"]
    new, vocab, temperature = options["max_new_tokens"], cfg["vocab_size"], options["temperature"]
    ids, offsets, tokens, logprobs, d_offsets, d_pos, d_ids, d_lp = _rows(window_parts)
    sizes = np.diff(offsets)
    finite = np.add.reduceat(np.isfinite(logprobs).astype(np.int64), offsets[:-1]) if len(tokens) else sizes
    ordered = np.array([drafts_in_order(d_pos[a:b], d_lp[a:b], new) for a, b in zip(d_offsets[:-1], d_offsets[1:])])
    wrong_length = int(np.count_nonzero((sizes != new) | (finite != sizes) | ~ordered))
    outside = int(np.count_nonzero((tokens < 0) | (tokens >= vocab)) + np.count_nonzero((d_ids < 0) | (d_ids >= vocab)))

    chosen = _sample(ids, pool, rules["sample_rows"], seed)
    first = {int(i): int(np.flatnonzero(ids == i)[0]) for i in chosen}  # its first delivery in the window
    tok = HashingTokenizer(vocab, options["max_prompt_tokens"])
    prompt_tokens, lengths = tok.encode_batch([pool[i] for i in chosen])
    not_words = int(sum(n != min(len(pool[i].split()), options["max_prompt_tokens"]) for i, n in zip(chosen, lengths)))
    sequences, starts, answers, served, drafts = [], [], [], [], []
    for k, i in enumerate(chosen):
        r = first[int(i)]
        a, b = offsets[r], offsets[r + 1]
        da, db = d_offsets[r], d_offsets[r + 1]
        answers.append(tokens[a:b])
        served.append(logprobs[a:b])
        drafts.append((d_pos[da:db], d_ids[da:db], d_lp[da:db]))
        sequences.append(np.concatenate([prompt_tokens[k, :lengths[k]], tokens[a:b]]).astype(np.int32))
        starts.append(int(lengths[k]) - 1)
    got = ref.forward_many(cfg, seed, sequences, precisions=("f32", "fp8") if control else ("f32",),
                           logits_from=starts, pad_to=options["max_prompt_tokens"] + new)
    main = [l[:len(a)] for l, a in zip(got["f32"], answers)]  # the last position predicts a token nobody asked for
    truth = [_log_softmax(l) for l in main]
    at_temperature = [_tempered(l, temperature) for l in main]
    entropy = [-(np.exp(l) * l).sum(-1) for l in at_temperature]
    mtp = [_log_softmax(l) for l in got["mtp.f32"]]
    margin = rules["logprob_gap_max"] + rules["draft_logprob_gap_max"]
    not_residual = 0
    for lt, l, ans, (pos, did, _) in zip(at_temperature, got["mtp.f32"], answers, drafts):
        rejected = pos[ans[pos] != did]
        c = ans[rejected]
        not_residual += int(np.count_nonzero(lt[rejected, c] < _tempered(l, temperature)[rejected - 1, c] - margin))

    def numbers(lp_served, chose, q_served) -> Dict[str, Dict[str, float]]:
        gap = draft_gap = 0.0
        excess, count, drafted, stood = 0.0, 0, 0, 0
        for lr, lt, h, lq, lp, ans, mine, (pos, did, _), qd in zip(truth, at_temperature, entropy, mtp, lp_served,
                                                                   answers, chose, drafts, q_served):
            at = np.arange(len(ans))
            gap = max(gap, float(np.max(np.abs(lr[at, ans] - lp), initial=0.0)))
            excess += float(np.sum(-lt[at, mine] - h))
            count += len(ans)
            if len(pos):
                draft_gap = max(draft_gap, float(np.max(np.abs(lq[pos - 1, did] - qd))))
                drafted += len(pos)
                stood += int(np.count_nonzero(ans[pos] == did))
        mean = excess / max(count, 1)
        return {
            "logprob_gap": {"value": gap, "limit": rules["logprob_gap_max"]},
            "draft_logprob_gap": {"value": draft_gap, "limit": rules["draft_logprob_gap_max"]},
            "sampled_nll_excess": {"value": abs(mean), "limit": rules["sampled_nll_excess_max"]},
            "sampled_nll_excess_signed": {"value": mean, "limit": None},
            f"answers_not_{new}_tokens": {"value": wrong_length, "limit": 0},
            "token_ids_outside_slice": {"value": outside, "limit": 0},
            "ids_out_of_sequence": {"value": out_of_sequence(id_stream, len(pool)), "limit": 0},
            "prompt_tokens_not_words": {"value": not_words, "limit": 0},
            "corrections_not_from_residual": {"value": not_residual, "limit": 0},
            "rows_compared": {"value": int(len(chosen)), "limit": None},
            "tokens_compared": {"value": count, "limit": None},
            "drafts_compared": {"value": drafted, "limit": None},
            "draft_accept_share": {"value": stood / drafted if drafted else 0.0, "limit": None},
        }

    out = {"numbers": numbers(served, answers, [d[2] for d in drafts]), "failed": wrong_length}
    if control:  # the reference in fp8 answers for the sampled rows in the program's place
        low = [l[:len(a)] for l, a in zip(got["fp8"], answers)]
        rng = np.random.default_rng([seed, 0xF8])
        drawn = []
        for l in low:  # tokens drawn from the control's own distributions
            prob = np.exp(_tempered(l, temperature))
            drawn.append((prob.cumsum(-1) < rng.random((len(l), 1))).sum(-1).clip(0, l.shape[-1] - 1))
        low_q = [_log_softmax(l) for l in got["mtp.fp8"]]
        out["control"] = numbers([_log_softmax(l)[np.arange(len(a)), a] for l, a in zip(low, answers)], drawn,
                                 [lq[pos - 1, did] if len(pos) else np.zeros(0) for lq, (pos, did, _) in zip(low_q, drafts)])
    return out
