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

**Slot state is the model's.** The batcher never looks inside it: it asks the
model for ``init_state(slots, positions)``, for a ``prefill`` that advances
some slots over one chunk of their prompts with the true lengths known, for a
``decode`` step over all slots, and for ``copy_state(state, src, dst)``.
``models/lm.DecoderLM`` keeps key/value rows; ``models/granite_hybrid`` keeps
SSM state and a conv tail beside them; ``models/longcat_flash`` keeps one
latent row a token an attention (576 values where 64 heads' keys and values
would be 20,480), writes only a prefill's chunk into it and reads only the
blocks a chunk attends; ``models/olmo_hybrid`` keeps a delta rule's state and a
conv tail in three layers of four and 30 heads' key/value rows in the fourth;
``models/deepseek_v32`` keeps two row leaves a layer, the latent row and the key
of the indexer that selects which rows a query attends (128 values beside 576).

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
the model counts of a round from its prompts' lengths (``prefill_counts``, if it has one: ``models/deepseek_v32``
gives ``index_pairs``, the pairs its indexer scores, = ``pairs``, and ``selected_pairs``, the pairs its selection
keeps: sum over prompt positions of min(position + 1, ``index_topk``));
``block_rows``: the (row, block of ``chunk`` positions) pairs its calls attend in which the row holds a query,
of ``padded_block_rows`` = sum over calls of rows a call x (the deepest row's block + 1) that calls of static
shape span),
``serve.copy_state``,
``serve.decode_step`` (one turn of the decode loop, from before the key split to after the step's bookkeeping:
``active``, ``slots``, the model's counts of the step, e.g. ``moe.held_assignments``, and ``first`` on the step
that traces and compiles or loads the program) with two children, ``serve.dispatch`` (the call of the decode program,
from entry to return) and ``serve.fetch`` (the step's one fetch: ``arrays``, the leaves it brings). Between the
first and the last step of a run every instant of the loop lies in one of ``serve.decode_step``, ``serve.prefill``
and ``serve.copy_state``; what of a step lies in neither child is its own, before the dispatch or after the fetch.
``serve.prefill`` and ``serve.decode_step`` also carry what the model noted on
them while its program traced (``moe`` = ``grouped`` | ``xla``: the path of the
routed experts' grouped products, ``models/decoders.grouped_mlp``; ``mla`` =
``fused`` | ``expanded`` | ``absorbed``: the latent attention's, ``models/longcat_flash``;
``delta`` = ``chunked`` | ``recurrent``: the gated delta rule's, ``models/olmo_hybrid``;
``dsa`` = ``masked`` (| ``gathered``, no program yet): how ``models/deepseek_v32``'s attention applies its
selection: every block a row holds expanded and scored with the keys not kept masked out, or the kept rows gathered).
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


class ContinuousBatcher:
    """Slot-based continuous batching over a model's own slot state."""

    #: The static prefill chunk (shorter where no prompt may be that long): the
    #: shorter the chunk, the less of a prompt's last one is padding.
    DEFAULT_CHUNK = 512
    #: Tokens one prefill call aims at, as chunk x prompts. What a held expert
    #: sees of them is the model's and the cut's: ~280 rows a call under
    #: granite-4.0-h-small's 72-way top-10 router with half the experts held
    #: (as in its deployment), ~32 under LongCat-Flash-Chat's 768-way top-12 with
    #: 16 of 512 held (its deployment's expert would see ~1,024: every chip's
    #: tokens reach it there, and here only this chip's).
    PREFILL_TOKENS = 2048

    def __init__(self, model, params, num_slots: int = 8,
                 max_seq_len: Optional[int] = None, temperature: float = 0.0,
                 eos_id: Optional[int] = 2, seed: int = 0,
                 prefill_chunk: Optional[int] = None,
                 max_prompt_tokens: Optional[int] = None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.B = num_slots
        limit = getattr(self.cfg, "max_seq_len", None)
        self.S = min(max_seq_len or limit, limit or max_seq_len)
        self.temperature = temperature
        self.eos_id = eos_id  # None: a sequence ends by its budget alone
        self._key = jax.random.PRNGKey(seed)
        # Room for >= 1 generated token; the caller may promise shorter prompts.
        self.max_prompt = min(int(max_prompt_tokens or self.S - 2), self.S - 2)
        # One static chunk, a multiple of what the model's own scan asks for
        # (``prefill_chunk`` is for tests: a small one makes a short prompt many chunks).
        mult = getattr(model, "prefill_multiple", 1)
        self.chunk = -(-int(prefill_chunk or min(self.DEFAULT_CHUNK, self.max_prompt)) // mult) * mult
        self.prefill_rows = max(1, min(self.PREFILL_TOKENS // self.chunk, self.B))
        # Positions a slot holds: every prompt chunk lies inside, padding too.
        self.positions_held = max(self.S, -(-self.max_prompt // self.chunk) * self.chunk)
        self.state = model.init_state(self.B, self.positions_held)
        self.cur_logits = jnp.zeros((self.B, model.vocab_size), jnp.float32)
        # Host-side bookkeeping: handed to each step, never read back.
        self.positions = np.zeros((self.B,), np.int32)
        self.active = np.zeros((self.B,), bool)
        self.slots = [_Slot() for _ in range(self.B)]
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
        kept = jnp.where(final[:, None], logits, cur_logits[slots])
        return state, cur_logits.at[slots].set(kept)

    def _copy_impl(self, state, cur_logits, src, dst):
        """Share a prefill: slot ``src``'s state and next-token logits into ``dst``."""
        return self.model.copy_state(state, src, dst), cur_logits.at[dst].set(cur_logits[src])

    def _decode_impl(self, params, state, cur_logits, positions, active, key):
        if self.temperature <= 0.0:
            tok = jnp.argmax(cur_logits, axis=-1).astype(jnp.int32)
        else:
            tok = jax.random.categorical(
                key, cur_logits / self.temperature, axis=-1).astype(jnp.int32)
        tok = jnp.where(active, tok, 0)
        logprob = jnp.take_along_axis(jax.nn.log_softmax(cur_logits, axis=-1), tok[:, None], axis=-1)[:, 0]
        state, logits, counts = self.model.decode(params, state, tok, positions, active)
        return state, logits, {"tok": tok, "logprob": logprob, "counts": counts}

    def _repeat_noted(self, sp) -> None:
        """A model notes its choice of path on the open span while its program
        traces (``decoders.grouped_mlp``: ``moe``; ``latent_attention``: ``mla``; ``olmo_hybrid``: ``delta``, ``attn``;
        ``deepseek_v32``: ``dsa``); a
        call that traces nothing repeats what the trace chose."""
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
        lens = np.asarray([len(req.tokens) for req, _ in todo], np.int64)
        row_chunks = -(-lens // T)
        calls = prefill_schedule(row_chunks.tolist(), g)
        with device_span("serve.prefill", slot=todo[0][1], rows=len(todo), tokens=int(lens.sum()),
                         padded_tokens=g * T * len(calls), chunks=len(calls),
                         mixed_calls=sum(len({c for _, c in call}) > 1 for call in calls),
                         row_chunks=int(row_chunks.sum()), pairs=int((lens * (lens + 1) // 2).sum()),
                         block_rows=int((row_chunks * (row_chunks + 1) // 2).sum()),
                         padded_block_rows=g * sum(max(c for _, c in call) + 1 for call in calls)) as sp:
            if self._prefill is None:
                sp.count["first"] = 1  # this call traces and compiles (or loads) the program
            # what the model counts of a round from its prompts' lengths (``deepseek_v32``: the pairs its selection keeps)
            sp.count.update(getattr(self.model, "prefill_counts", lambda lens: {})(lens))
            fn = self._prefill_fn()
            for call in calls:
                taken = [todo[i][1] for i, _ in call]
                # Rows of the call that carry no prompt name other slots, at length 0 (left as they are) and from
                # position 0 (an attention's block loop runs as deep as the call's deepest row). Such a call holds
                # every prompt of the round that still has a chunk, so none of those slots is in the middle of one.
                spare = [s for s in range(self.B) if s not in taken][:g - len(call)]
                tokens = np.zeros((g, T), np.int32)
                starts, here, final = np.zeros((g,), np.int32), np.zeros((g,), np.int32), np.zeros((g,), bool)
                for row, (i, c) in enumerate(call):
                    part = todo[i][0].tokens[c * T:(c + 1) * T]
                    tokens[row, :len(part)] = part
                    starts[row], here[row], final[row] = c * T, len(part), c == row_chunks[i] - 1
                self.state, self.cur_logits = fn(self.params, self.state, self.cur_logits, tokens,
                                                 np.asarray(taken + spare, np.int32), starts, here, final)
            self._repeat_noted(sp)
        for req, slot in todo:
            self._admit_host(req, slot)

    def _admit_host(self, req: Request, slot: int) -> None:
        self.active[slot] = True
        self.positions[slot] = len(req.tokens)
        self.slots[slot] = _Slot(request=req, remaining=req.max_new_tokens)

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
                self._key, sub = jax.random.split(self._key)
                with device_span("serve.dispatch"):
                    self.state, self.cur_logits, out = self._decode(
                        self.params, self.state, self.cur_logits, self.positions, self.active, sub)
                with device_span("serve.fetch") as fetch:
                    out = jax.device_get(out)  # the step's one wait for the device
                    if first:
                        self._fetched_arrays = len(jax.tree_util.tree_leaves(out))
                    fetch.count["arrays"] = self._fetched_arrays
                sp.count.update({f"moe.{k}": int(v) for k, v in out["counts"].items()})
                self._repeat_noted(sp)
                steps += 1
                self.positions += self.active
                for slot in np.flatnonzero(self.active):
                    s = self.slots[slot]
                    t = int(out["tok"][slot])
                    s.generated.append(t)
                    s.logprobs.append(float(out["logprob"][slot]))
                    s.remaining -= 1
                    if t == self.eos_id or s.remaining <= 0 \
                            or self.positions[slot] >= self.S - 1:
                        self._retire(int(slot), results)
        self.decode_steps = steps
        done = [results.get(i, _Slot()) for i in range(len(requests))]
        self.last_logprobs = [s.logprobs for s in done]
        return [s.generated for s in done]


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
