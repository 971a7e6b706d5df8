"""Continuous-batching LLM serving engine with prefix routing.

Reference: the vLLM streaming sink + executors
(src/daft-local-execution/src/streaming_sink/vllm.rs,
daft/execution/vllm.py:111-160) — the reference hands prompts to vLLM's
AsyncLLMEngine, whose continuous batching keeps the GPU busy by retiring
finished sequences and admitting new ones mid-decode, and optionally routes
shared-prefix prompts to the same replica.

TPU-first re-design: XLA needs static shapes, so the engine holds a FIXED
pool of decode slots (batch dim B) and a fixed number of positions; admission
and retirement mutate slot state in jitted programs, and ONE jitted decode
step advances every active slot a token per iteration. Mixed-length workloads
win exactly where vLLM wins: a finished slot is refilled immediately instead
of idling until the batch's longest sequence completes.

**The serving protocol** (``ServedModel``) is all the batcher knows of a
model. Slot state is the model's and the batcher never looks inside it: it asks
for ``init_state(slots, positions)``, a ``prefill`` that advances some slots
over one chunk of their prompts with the true lengths known, a ``decode`` step
of one token over all slots, ``copy_state(state, src, dst)`` and
``vocab_size``. Optional hooks have their defaults on the base class:
``block_length``, ``draft_length``, ``prefill_multiple``, ``max_seq_len`` and
``prefill_counts``. Which leaves of the state are rows a position (an
attention's keys and values, latent rows) and which are recurrent state is read
from their shapes: ``init_state`` at two position counts, as shapes only.

**A block a step.** A model that declares ``block_length`` L gets another
decode program under the same name: one dispatch runs the model's
``block_step`` (it also gives ``denoising_steps`` and ``mask_id``),
``denoising_steps`` forwards of every slot's block over its committed rows,
each fixing some of the block's masked positions, then the forward that commits
the block's rows. It returns each slot's L tokens, the log-probability each
fixed token had at the pass that fixed it, and that pass. Greedy only. The host
loop is one for both kinds: a token model's step is read as a block of one. A
prompt prefills its whole blocks only; its tail (P mod L tokens; none for a
token model) waits, fixed, in the slot's first block, whose other positions
hold the ``mask_id`` (a slot's block is host state, set at admission, so a
shared prefill's copy gets it too). After each step the loop appends what the
step decided, moves the slot on, and retires it on its budget (an answer is its
first ``max_new_tokens``), at an end-of-sequence id or where no further step
fits.

**A draft a step.** A model that declares ``draft_length`` (1: one drafted token
a step) gets a third decode program under the same name, the speculative step. A
slot holds a pending token t_m, emitted and not yet run by the model. One
dispatch runs the model's ``draft`` (a self-drafting block over the positions
whose hidden state and next token became known since it last ran; it gives the
draft's logits q), draws the draft d from q, runs the model's ``verify`` (the
model over [t_m, d] at m and m + 1, writing both rows; it gives p_{m+1} and
p_{m+2}) and the rejection sampler (``rejection_sample``): d is accepted with
probability min(1, p_{m+1}(d) / q(d)), and then b ~ p_{m+2} follows it; else c ~
normalise(max(0, p_{m+1} - q)) is emitted. So a slot gains one or two tokens a
step, distributed exactly as the model's own p; at temperature 0, d = argmax q is
accepted where it is argmax p_{m+1}, which emits what greedy decoding one token a
step would. A slot's first token after its prefill is drawn in its first step,
from the prefill's logits. The acceptance rule is the batcher's; each emitted
token's log-probability is log p of the logits it was drawn from, and each step
also returns the draft and log q(d). The host loop appends what each slot
emitted, a count of its own, and truncates an answer at its budget.

**Prefill is chunked at one static length**, so prompts of any length share
one executable: a prompt runs as ceil(P / chunk) calls that carry state, the
last one right-padded. As many prompts advance in one call as bring it to
about ``PREFILL_TOKENS`` tokens, **each at its own depth**: a call takes the
next chunk of whichever prompts of the admission round still have one, those
with the most chunks left first (``prefill_schedule``), so a round runs
max(its longest prompt's chunks, ceil(its prompts' chunks / prompts a call))
calls, the fewest any schedule can, whatever the spread of its lengths; a
model's ``prefill`` takes the chunk's first position row by row. Identical
prompts admitted in the same round share one prefill through ``copy_state``
(prefix routing: requests are ordered by length and prompt hash before
admission, so identical prompts are adjacent, the reference's
do_prefix_routing analogue, and which prompts share a round does not hang on
their hashes); a later round prefills again, since recurrent state, unlike
key/value rows, has moved on with its slot.

Spans (``profiling.device_span``): ``serve.prefill`` (one admission round's calls:
``slot``, ``rows``: the prompts prefilled, ``tokens``, ``chunks``: the calls, ``padded_tokens`` = calls x prompts
a call x ``chunk``, ``row_chunks``, ``first``; ``mixed_calls``: the calls whose prompts stood at unlike depths;
``pairs``: the causal (query, key) pairs of the round's prompts, an attention's least work; beside it whatever
the model counts of a round from its prompts' lengths (``prefill_counts``; ``tokens`` are those the calls ran,
and a block model's prompt tails wait for its first block);
``block_rows``: the (row, block of ``chunk`` positions) pairs its calls attend in which the row holds a query,
of ``padded_block_rows`` = sum over calls of rows a call x (the deepest row's block + 1) that calls of static
shape span),
``serve.copy_state``,
``serve.decode_step`` (one turn of the decode loop, from before the key split to after the step's bookkeeping:
``active``, ``slots``, the model's counts of the step, e.g. ``moe.held_assignments``, and ``first`` on the step
that traces and compiles or loads the program; for a drafting model ``drafted`` (active),
``accepted``, ``tokens`` (emitted), ``forwards`` (2), ``verify_positions`` (2 x active) and ``keys`` (the
positions the active slots' verified queries see); for a block model ``tokens``, the tokens it commits, L x active,
``forwards``, ``denoising_steps`` + 1, ``masked``, the positions its passes decided, ``block``, L, and ``keys``, the
positions the active slots' queries see: their committed rows and the block) with two children, ``serve.dispatch`` (the call of the decode program,
from entry to return) and ``serve.fetch`` (the step's one fetch: ``arrays``, the leaves it brings). Between the
first and the last step of a run every instant of the loop lies in one of ``serve.decode_step``, ``serve.prefill``
and ``serve.copy_state``; what of a step lies in neither child is its own, before the dispatch or after the fetch.
``serve.prefill`` and ``serve.decode_step`` also carry what the model noted on
them while its program traced (``decoders.note_on_serving_span``: which path a
layer took, e.g. ``moe`` = ``grouped`` | ``xla``); each model's docstring says
which keys it notes.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.profiling import device_span


@dataclass
class Request:
    tokens: np.ndarray        # (P,) int32, unpadded
    max_new_tokens: int = 32
    request_id: int = 0
    prefix_key: Optional[str] = None  # set by the router


@dataclass
class _Slot:
    request: Optional[Request] = None
    generated: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    unmask_steps: List[int] = field(default_factory=list)  # a block model's: the pass that fixed each token
    drafts: List[Tuple[int, int, float]] = field(default_factory=list)  # a drafting model's: (answer index, id, log q)
    remaining: int = 0


def prefill_schedule(chunks: Sequence[int], rows: int) -> List[List[Tuple[int, int]]]:
    """The prefill calls of one admission round whose prompt ``i`` runs
    ``chunks[i]`` chunks: each call is up to ``rows`` pairs (prompt, the chunk
    of it that the call runs), the prompts with the most chunks left first, ties
    to the earlier prompt. A prompt gives a call at most one chunk (chunk c + 1
    reads what chunk c wrote), so no schedule has fewer calls than
    max(max(chunks), ceil(sum(chunks) / rows)), and this one has as many."""
    done = [0] * len(chunks)
    calls = []
    while True:
        call = sorted((i for i, n in enumerate(chunks) if done[i] < n), key=lambda i: (done[i] - chunks[i], i))[:rows]
        if not call:
            return calls
        calls.append([(i, done[i]) for i in call])
        for i in call:
            done[i] += 1


class ServedModel:
    """The serving protocol: what ``ContinuousBatcher`` asks of a model, with the
    optional hooks' defaults. Required: ``init_state(slots, positions)`` -> slot
    state (every leaf's first axis the slot); ``prefill(params, state, tokens,
    slots, starts, lengths)`` -> (state, next-token logits (B, V) or None,
    counts); ``decode(params, state, tokens, positions, active)`` -> (state,
    logits, counts), or for a model with ``block_length``
    ``block_step(params, state, tokens, tail, positions, active)`` -> (state,
    {"tok", "logprob", "step"} (slots, L) each, counts); ``vocab_size``. A model
    with ``draft_length`` also has ``draft(params, state, tokens, positions,
    pairs, active)`` -> (state, the draft's logits (slots, V), counts): its
    drafting block over the two positions before each slot's pending token
    (tokens, positions (slots, 2); ``pairs`` (slots,): how many of the two are
    new since it last ran, 1 or 2, the first left as it was where 1), reading the
    hidden states its state kept of the model's last forward; and
    ``verify(params, state, tokens, positions, active)`` -> (state, logits
    (slots, draft_length + 1, V), counts): the model over the pending token and
    the draft at their positions (slots, draft_length + 1), writing their rows
    and keeping their hidden states."""

    #: None: a token a step (``decode``); L: a block of L tokens a step (``block_step``).
    block_length: Optional[int] = None
    #: None: no draft; D: the model drafts D tokens a step (``draft``) and the batcher verifies them (``verify``).
    draft_length: Optional[int] = None
    #: A prefill chunk is a whole multiple of this many tokens.
    prefill_multiple: int = 1
    #: The longest sequence the model takes, if it has a limit of its own.
    max_seq_len: Optional[int] = None

    def prefill_counts(self, lengths) -> Dict[str, int]:
        """Host-side counts of an admission round whose prompts have ``lengths`` tokens, for ``serve.prefill``."""
        return {}

    def copy_state(self, state, src, dst):
        """Slot ``src``'s state into slot ``dst``, whatever the leaves hold."""
        return jax.tree_util.tree_map(lambda a: a.at[dst].set(a[src]), state)


def rejection_sample(key, d, p_logits, q_logits, temperature: float):
    """The verdict on drafts ``d`` (rows,) drawn from q: each accepted with
    probability min(1, p(d) / q(d)), and where it is not, the correction c ~
    normalise(max(0, p - q)), so that what is emitted (d where accepted, else c)
    is distributed as p. p and q are softmax(logits / temperature), (rows, V)
    each. At temperature 0 (d = argmax q): accepted where d is argmax p, c =
    argmax p. -> (accepted, c), (rows,) each."""
    if temperature <= 0.0:
        c = jnp.argmax(p_logits, axis=-1).astype(jnp.int32)
        return d == c, c
    k_accept, k_correct = jax.random.split(key)
    p, q = (jax.nn.softmax(x / temperature, axis=-1) for x in (p_logits, q_logits))
    p_d, q_d = (jnp.take_along_axis(x, d[:, None], axis=-1)[:, 0] for x in (p, q))
    accepted = jax.random.uniform(k_accept, d.shape) * q_d < p_d
    excess = residual(p, q)
    # where p equals q nothing is rejected: the correction is then p's own draw
    odds = jnp.where(jnp.sum(excess, -1, keepdims=True) > 0, jnp.log(excess), p_logits / temperature)
    return accepted, jax.random.categorical(k_correct, odds, axis=-1).astype(jnp.int32)


def residual(p, q):
    """What a rejection draws from, unnormalised: max(0, p - q)."""
    return jnp.maximum(p - q, 0.0)


def state_bytes_by_kind(model: ServedModel, slots: int, positions: int) -> Dict[str, int]:
    """``model.init_state(slots, positions)`` in two kinds, from shapes alone (nothing is drawn or placed):
    ``kv_bytes``, the leaves that grow with the positions a slot holds (rows a position, whatever tile a model
    rounds them up to: 4,096 positions more is more than any), and ``recurrent_bytes``, the rest."""
    held = jax.eval_shape(lambda: model.init_state(slots, positions))
    more = jax.eval_shape(lambda: model.init_state(slots, positions + 4096))
    kinds = {"kv_bytes": 0, "recurrent_bytes": 0}
    for a, b in zip(jax.tree_util.tree_leaves(held), jax.tree_util.tree_leaves(more)):
        kinds["kv_bytes" if a.shape != b.shape else "recurrent_bytes"] += a.size * a.dtype.itemsize
    return kinds


class ContinuousBatcher:
    """Slot-based continuous batching over a model's own slot state."""

    #: The static prefill chunk (shorter where no prompt may be that long): the
    #: shorter the chunk, the less of a prompt's last one is padding.
    DEFAULT_CHUNK = 512
    #: Tokens one prefill call aims at, as chunk x prompts. What a held expert
    #: sees of them is the model's and the cut's: ~280 rows a call under a
    #: 72-way top-10 router with half the experts held, ~32 under a 768-way
    #: top-12 with 16 of 512 held (a deployment's expert would see ~1,024 there:
    #: every chip's tokens reach it, and here only this chip's).
    PREFILL_TOKENS = 2048

    def __init__(self, model: ServedModel, params, num_slots: int = 8,
                 max_seq_len: Optional[int] = None, temperature: float = 0.0,
                 eos_id: Optional[int] = 2, seed: int = 0,
                 prefill_chunk: Optional[int] = None,
                 max_prompt_tokens: Optional[int] = None):
        self.model = model
        self.params = params
        L = self.L = model.block_length  # a block a step, not a token
        D = self.D = model.draft_length  # a drafted token a step, verified
        from daft_tpu.errors import DaftValueError

        if L and D:
            raise DaftValueError("a model drafts tokens (draft_length) or generates a block a step (block_length), "
                                 "not both")
        if D not in (None, 1):
            raise DaftValueError(f"draft_length={D}: the speculative step verifies one drafted token a slot")
        if L and temperature > 0.0:
            raise DaftValueError("a model that generates a block a step is served greedily here (temperature 0); "
                                 "sampled answers need a decoder of one token a step or one that drafts")
        self.B = num_slots
        limit = model.max_seq_len
        self.S = min(max_seq_len or limit, limit or max_seq_len)
        self.temperature = temperature
        self.eos_id = eos_id  # None: a sequence ends by its budget alone
        self._key = jax.random.PRNGKey(seed)
        # Room for >= 1 generated token; the caller may promise shorter prompts.
        self.max_prompt = min(int(max_prompt_tokens or self.S - 2), self.S - 2)
        # One static chunk, a multiple of what the model's own scan asks for
        # (``prefill_chunk`` is for tests: a small one makes a short prompt many chunks).
        mult = model.prefill_multiple
        self.chunk = -(-int(prefill_chunk or min(self.DEFAULT_CHUNK, self.max_prompt)) // mult) * mult
        self.prefill_rows = max(1, min(self.PREFILL_TOKENS // self.chunk, self.B))
        # Positions a slot holds: every prompt chunk lies inside, padding too.
        self.positions_held = max(self.S, -(-self.max_prompt // self.chunk) * self.chunk)
        self.state = model.init_state(self.B, self.positions_held)
        self.state_bytes = state_bytes_by_kind(model, self.B, self.positions_held)
        # A block model holds no next-token logits: what follows a prompt is its first block.
        self.cur_logits = None if L else jnp.zeros((self.B, model.vocab_size), jnp.float32)
        # Host-side bookkeeping: handed to each step, never read back. A token model's step is a block of one.
        self.per_step = L or 1
        # A slot whose position passes this retires: a block model's next block would not end inside the S positions;
        # a token model keeps its last position spare.
        self.room = self.S - (L or 2)
        self.positions = np.zeros((self.B,), np.int32)  # where each slot's next step starts
        self.active = np.zeros((self.B,), bool)
        self.slots = [_Slot() for _ in range(self.B)]
        # Each slot's block: the prompt's tail (its first ``tail`` tokens, fixed), then the mask id. Only a block
        # model's goes to the device.
        self.mask_id = model.mask_id if L else 0
        self.block = np.full((self.B, self.per_step), self.mask_id, np.int32)
        self.tail = np.zeros((self.B,), np.int32)
        # A drafting model's: each slot's last two tokens (the second pending), how many of the pairs before it its
        # drafting block has not run (1 or 2), and whether its first token is still to be drawn from the prefill's
        # logits. Only a drafting model's go to the device.
        self.last_two = np.zeros((self.B, 2), np.int32)
        self.pairs = np.ones((self.B,), np.int32)
        self.fresh = np.zeros((self.B,), bool)
        self._prefill = None  # jitted at first use: tests/test_serving.py wraps _prefill_impl on an instance before
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 2))
        self._copy = jax.jit(self._copy_impl, donate_argnums=(0, 1))
        self.decode_steps = 0
        self._fetched_arrays = 0  # leaves a step's fetch brings, a constant of the program: counted on the first step
        self._noted: Dict[str, dict] = {}  # span name -> what the model noted while that program traced

    # -- jitted programs ------------------------------------------------- #
    def _prefill_impl(self, params, state, cur_logits, tokens, slots, starts, lengths, final):
        """One chunk for ``slots``; a row whose prompt ends here (``final``)
        leaves its next-token logits in ``cur_logits``."""
        state, logits, _ = self.model.prefill(params, state, tokens, slots, starts, lengths)
        if cur_logits is None:  # a block model: no token follows a prefill
            return state, None
        kept = jnp.where(final[:, None], logits, cur_logits[slots])
        return state, cur_logits.at[slots].set(kept)

    def _copy_impl(self, state, cur_logits, src, dst):
        """Share a prefill: slot ``src``'s state and next-token logits into ``dst``."""
        state = self.model.copy_state(state, src, dst)
        return state, None if cur_logits is None else cur_logits.at[dst].set(cur_logits[src])

    def _decode_impl(self, params, state, cur_logits, positions, active, key, block=None, spec=None):
        if block is not None:  # a block model (no logits): the slots' blocks, (tokens, tail)
            state, out, counts = self.model.block_step(params, state, *block, positions, active)
            return state, cur_logits, dict(out, counts=counts)
        if spec is not None:  # a drafting model: (last two tokens, new pairs, fresh)
            return self._speculate(params, state, cur_logits, positions, active, key, *spec)
        tok = jnp.where(active, self._choose(key, cur_logits), 0)
        logprob = jnp.take_along_axis(jax.nn.log_softmax(cur_logits, axis=-1), tok[:, None], axis=-1)[:, 0]
        state, logits, counts = self.model.decode(params, state, tok, positions, active)
        return state, logits, {"tok": tok, "logprob": logprob, "counts": counts}

    def _speculate(self, params, state, cur_logits, positions, active, key, last_two, pairs, fresh):
        """The speculative step: a fresh slot's pending token t_m drawn from its prefill's logits, the draft d from
        the drafting block, the model over [t_m, d], the rejection sampler. -> (state, cur_logits, {"tok", "logprob"
        (slots, 3): t_m, the draft or its correction, the token after an accepted draft; "accepted", "draft",
        "draft_logprob", "counts"})."""
        from daft_tpu.models.decoders import add_counts  # here: decoders imports the models, which import this module

        k_first, k_draft, k_judge, k_next = jax.random.split(key, 4)
        with jax.named_scope("sample"):
            pending = jnp.where(fresh, self._choose(k_first, cur_logits), last_two[:, 1])
        m = positions[:, None]
        state, q_logits, drafted = self.model.draft(
            params, state, jnp.stack([last_two[:, 0], pending], 1), m + jnp.asarray([-2, -1]), pairs, active)
        with jax.named_scope("sample"):
            d = self._choose(k_draft, q_logits)
        state, logits, verified = self.model.verify(
            params, state, jnp.stack([pending, d], 1), m + jnp.arange(2)[None, :], active)
        with jax.named_scope("sample"):
            accepted, c = rejection_sample(k_judge, d, logits[:, 0], q_logits, self.temperature)
            accepted = accepted & active
            bonus = self._choose(k_next, logits[:, 1])
            tok = jnp.stack([pending, jnp.where(accepted, d, c), bonus], 1)
            lp = jax.nn.log_softmax(jnp.concatenate([cur_logits[:, None], logits], 1), axis=-1)  # (slots, 3, V)
            logprob = jnp.take_along_axis(lp, tok[..., None], axis=-1)[..., 0]
            draft_logprob = jnp.take_along_axis(jax.nn.log_softmax(q_logits, -1), d[:, None], axis=-1)[:, 0]
        return state, cur_logits, {"tok": tok, "logprob": logprob, "accepted": accepted, "draft": d,
                                   "draft_logprob": draft_logprob, "counts": add_counts(drafted, verified)}

    def _choose(self, key, logits):
        """A token a row from ``logits``: argmax at temperature 0, else drawn from softmax(logits / temperature)."""
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.temperature, axis=-1).astype(jnp.int32)

    def _repeat_noted(self, sp) -> None:
        """A model notes its choice of path on the open span while its program
        traces (``decoders.note_on_serving_span``); a call that traces nothing
        repeats what the trace chose."""
        noted = self._noted.setdefault(sp.name, {})
        noted.update({k: v for k, v in sp.count.items() if isinstance(v, str)})
        sp.count.update(noted)

    def _prefill_fn(self):
        if self._prefill is None:
            self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1, 2))
        return self._prefill

    # -- admission ------------------------------------------------------- #
    def _admit(self, queue: List[Request], free: List[int]) -> None:
        """Fill free slots from the queue: one prefill for each distinct
        prompt of the round, all of them through the same packed calls, then
        state copies for the repeats."""
        first: Dict[str, int] = {}  # prefix key -> the slot that prefills it, this round
        todo, copies = [], []
        for slot in free:
            if not queue:
                break
            req = queue.pop()
            src = first.get(req.prefix_key)
            if src is None:
                first[req.prefix_key] = slot
                todo.append((req, slot))
            else:
                copies.append((req, src, slot))
        self._prefill_round(todo)
        for req, src, dst in copies:
            with device_span("serve.copy_state", src=src, slot=dst):
                self.state, self.cur_logits = self._copy(self.state, self.cur_logits,
                                                         jnp.int32(src), jnp.int32(dst))
            self._admit_host(req, dst)

    def _prefill_round(self, todo) -> None:
        """Prefill the round's prompts (``todo``: (request, slot), in order of
        slot) in the calls ``prefill_schedule`` gives: every row of a call at
        its own chunk of its own prompt."""
        g, T = self.prefill_rows, self.chunk
        whole = np.asarray([len(req.tokens) for req, _ in todo], np.int64)
        lens = whole - whole % self.per_step  # a block model prefills whole blocks only
        row_chunks = -(-lens // T)
        calls = prefill_schedule(row_chunks.tolist(), g)
        with device_span("serve.prefill", slot=todo[0][1], rows=len(todo), tokens=int(lens.sum()),
                         padded_tokens=g * T * len(calls), chunks=len(calls),
                         mixed_calls=sum(len({c for _, c in call}) > 1 for call in calls),
                         row_chunks=int(row_chunks.sum()), pairs=int((lens * (lens + 1) // 2).sum()),
                         block_rows=int((row_chunks * (row_chunks + 1) // 2).sum()),
                         padded_block_rows=g * sum(max(c for _, c in call) + 1 for call in calls)) as sp:
            if self._prefill is None and calls:
                sp.count["first"] = 1  # this call traces and compiles (or loads) the program
            sp.count.update(self.model.prefill_counts(whole))
            fn = self._prefill_fn() if calls else None
            for call in calls:
                taken = [todo[i][1] for i, _ in call]
                # Rows of the call that carry no prompt name other slots, at length 0 (left as they are) and from
                # position 0 (an attention's block loop runs as deep as the call's deepest row). Such a call holds
                # every prompt of the round that still has a chunk, so none of those slots is in the middle of one.
                spare = [s for s in range(self.B) if s not in taken][:g - len(call)]
                tokens = np.zeros((g, T), np.int32)
                starts, here, final = np.zeros((g,), np.int32), np.zeros((g,), np.int32), np.zeros((g,), bool)
                for row, (i, c) in enumerate(call):
                    part = todo[i][0].tokens[c * T:min((c + 1) * T, lens[i])]
                    tokens[row, :len(part)] = part
                    starts[row], here[row], final[row] = c * T, len(part), c == row_chunks[i] - 1
                self.state, self.cur_logits = fn(self.params, self.state, self.cur_logits, tokens,
                                                 np.asarray(taken + spare, np.int32), starts, here, final)
            self._repeat_noted(sp)
        for req, slot in todo:
            self._admit_host(req, slot)

    def _admit_host(self, req: Request, slot: int) -> None:
        """The prompt's tail waits, fixed, in the slot's first block; the rest of the block is masked."""
        n = len(req.tokens) % self.per_step
        self.active[slot] = True
        self.positions[slot] = len(req.tokens) - n
        self.slots[slot] = _Slot(request=req, remaining=req.max_new_tokens)
        self.tail[slot] = n
        self.block[slot] = self.mask_id
        self.block[slot, :n] = req.tokens[len(req.tokens) - n:]
        # a drafting model's slot: its first token is drawn from the prefill's logits, and one pair is new
        self.last_two[slot], self.pairs[slot], self.fresh[slot] = (req.tokens[-1], 0), 1, True

    def _retire(self, slot: int, results: Dict[int, _Slot]) -> None:
        s = self.slots[slot]
        if s.request is not None:
            results[s.request.request_id] = s
        self.slots[slot] = _Slot()
        self.active[slot] = False

    # -- main loop ------------------------------------------------------- #
    def run(self, requests: Sequence[Request]) -> List[List[int]]:
        """Generate for all requests; returns token lists in request order
        (``last_logprobs`` holds each chosen token's log-probability)."""
        queue = list(requests)
        for i, r in enumerate(queue):
            if len(r.tokens) > self.max_prompt:
                from daft_tpu.errors import DaftValueError

                raise DaftValueError(
                    f"prompt of {len(r.tokens)} tokens exceeds the cache "
                    f"capacity ({self.S}); raise max_seq_len or truncate")
            r.request_id = i
            if r.prefix_key is None:
                r.prefix_key = hashlib.blake2b(
                    np.ascontiguousarray(r.tokens).tobytes(),
                    digest_size=8).hexdigest()
        # Prefix routing: identical prompts (one length, one key) are adjacent and share a prefill. Distinct prompts
        # are admitted in order of length: a round runs at least as many calls as its longest prompt has chunks, so
        # which prompts share a round, and with it a partition's calls, must not hang on how their hashes fall (16
        # documents of 1-15 thousand tokens on 8 slots: 11 + 31 calls of 4 x 512 in this order, PERF.md section 6).
        queue.sort(key=lambda r: (len(r.tokens), r.prefix_key, r.request_id))
        queue.reverse()  # pop() admits in sorted order
        results: Dict[int, _Slot] = {}
        steps = 0
        while queue or self.active.any():
            free = [i for i in range(self.B) if self.slots[i].request is None]
            if free and queue:
                self._admit(queue, free)
            # One decode step for the whole pool: the span is one turn of the loop, and whatever of it lies in
            # neither child is the step's own, told by where it lies (before the dispatch: the key split; after
            # the fetch: the bookkeeping).
            with device_span("serve.decode_step", active=int(self.active.sum()), slots=self.B) as sp:
                first = not self._fetched_arrays
                if first:
                    sp.count["first"] = 1  # this step traces and compiles (or loads) the program
                if self.L:  # what a block step commits and what its passes decide
                    n = self.per_step
                    sp.count.update(tokens=n * int(self.active.sum()), forwards=self.model.denoising_steps + 1,
                                    masked=int(((n - self.tail) * self.active).sum()), block=n,
                                    keys=int(((self.positions + n) * self.active).sum()))
                if self.D:  # what a speculative step drafts and verifies
                    active = int(self.active.sum())
                    sp.count.update(drafted=active, forwards=2, verify_positions=2 * active,
                                    keys=int(((self.positions + 2) * self.active).sum()))
                self._key, sub = jax.random.split(self._key)
                block = (self.block, self.tail) if self.L else None
                spec = (self.last_two, self.pairs, self.fresh) if self.D else None
                with device_span("serve.dispatch"):
                    self.state, self.cur_logits, out = self._decode(
                        self.params, self.state, self.cur_logits, self.positions, self.active, sub, block, spec)
                with device_span("serve.fetch") as fetch:
                    out = jax.device_get(out)  # the step's one wait for the device
                    if first:
                        self._fetched_arrays = len(jax.tree_util.tree_leaves(out))
                    fetch.count["arrays"] = self._fetched_arrays
                sp.count.update({f"moe.{k}": int(v) for k, v in out["counts"].items()})
                if self.D:  # what the step accepted and emitted (a fresh slot its first token too)
                    taken = out["accepted"][self.active]
                    sp.count.update(accepted=int(taken.sum()),
                                    tokens=int((1 + taken + self.fresh[self.active]).sum()))
                self._repeat_noted(sp)
                steps += 1
                self._take(out, results)
        self.decode_steps = steps
        done = [results.get(i, _Slot()) for i in range(len(requests))]
        self.last_logprobs = [s.logprobs for s in done]
        self.last_unmask_steps = [s.unmask_steps for s in done]
        self.last_drafts = [s.drafts for s in done]
        return [s.generated for s in done]

    def _take(self, out, results: Dict[int, _Slot]) -> None:
        """A step's bookkeeping (a token model's step read as a block of one): each active slot appends what the
        step decided (its block but the prompt's tail; a drafting model's: its pending token where the slot is
        fresh, the draft or its correction, and the token after an accepted draft), moves on by that, and retires
        on its budget, at an end-of-sequence id or where its next step does not fit (``room``); an answer is its
        first ``max_new_tokens`` tokens (and up to its end id)."""
        n = self.per_step
        tok, logprob = (np.reshape(out[k], (self.B, -1)) for k in ("tok", "logprob"))
        step = out.get("step")  # a block model's: the pass that fixed each token
        accepted = out.get("accepted")  # a drafting model's
        for slot in np.flatnonzero(self.active):
            s, tail = self.slots[slot], int(self.tail[slot])
            lo, hi = tail, n
            if accepted is not None:
                lo, hi = int(not self.fresh[slot]), 2 + int(accepted[slot])
                # the draft stood at the position after the pending token's
                at = int(self.positions[slot]) + 1 - len(s.request.tokens)
                s.drafts.append((at, int(out["draft"][slot]), float(out["draft_logprob"][slot])))
            new = tok[slot, lo:hi].tolist()
            s.generated += new
            s.logprobs += logprob[slot, lo:hi].tolist()
            if step is not None:
                s.unmask_steps += step[slot, lo:hi].tolist()
            s.remaining -= hi - lo
            if accepted is not None:
                self.positions[slot] += hi - 1
                self.last_two[slot], self.pairs[slot], self.fresh[slot] = tok[slot, hi - 2:hi], hi - 1, False
            else:
                self.positions[slot] += n
                self.tail[slot], self.block[slot] = 0, self.mask_id
            ended = self.eos_id in new
            if ended or s.remaining <= 0 or self.positions[slot] > self.room:
                keep = s.generated.index(self.eos_id) + 1 if ended else s.request.max_new_tokens
                s.generated, s.logprobs, s.unmask_steps = s.generated[:keep], s.logprobs[:keep], s.unmask_steps[:keep]
                s.drafts = [d for d in s.drafts if d[0] < len(s.generated)]
                self._retire(int(slot), results)


def generate_continuous(model, params, prompts: Sequence[np.ndarray],
                        max_new_tokens, num_slots: int = 8,
                        temperature: float = 0.0, seed: int = 0) -> List[List[int]]:
    """Convenience wrapper: prompts as unpadded int32 arrays; max_new_tokens
    scalar or per-request sequence."""
    if isinstance(max_new_tokens, int):
        max_new_tokens = [max_new_tokens] * len(prompts)
    reqs = [Request(tokens=np.asarray(p, np.int32), max_new_tokens=int(m))
            for p, m in zip(prompts, max_new_tokens)]
    batcher = ContinuousBatcher(model, params, num_slots=num_slots,
                                temperature=temperature, seed=seed)
    out = batcher.run(reqs)
    generate_continuous.last_decode_steps = batcher.decode_steps
    return out
