"""Continuous-batching request scheduler (serving example + JaxBackend).

Fixed-slot design: a decode batch of ``num_slots`` sequences steps together;
finished/empty slots are refilled from the queue between steps (prefill for
the incoming request, cache splice into the slot). This is the standard
TPU-serving shape: the decode step has a static (slots, 1) signature so it
compiles once, and admission happens on the host between steps. Admission
runs two jitted programs as well: a prefill traced once per prompt bucket
(the true length is a traced scalar) and a splice into a traced slot that
donates the batch cache and the token column, so it updates them in place.

The batcher marks its host work with profiler spans
(``jax.profiler.TraceAnnotation``), which land on the trace's clock
beside the device's planes and cost about a microsecond each when no
profiler runs: ``batcher.tick`` (a ``StepTraceAnnotation`` numbered by
tick), ``batcher.admit`` per request taken from the queue (``uid``,
``prompt_len``, ``bucket``) holding ``batcher.prefill`` (``traces``: how
often the admission programs have been traced so far) and
``batcher.splice``, ``batcher.decode`` (``active``, ``slots``) holding
``batcher.step``, and ``batcher.sync`` around each device-to-host read.
Every stat is a host integer known when its span opens.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.config import ModelConfig
from repro.serving.decode import SERVE_STEP_DONATE, make_serve_step

span = jax.profiler.TraceAnnotation

#: prompts right-pad to multiples of this before prefill, so the prefill
#: jit site sees a handful of shapes instead of one per distinct prompt
#: length. Exact only when a prompt fills its bucket. ``transformer.prefill``
#: returns the logits of the last (padded) position alone, so the read at
#: true_len - 1 clamps to the padded end; and an SSM layer's state runs on
#: through the pads. Attention rows [0, true_len) are unaffected by the
#: pads, but the cache's one ``len`` is the batch's (ROADMAP R2).
PREFILL_BUCKET = 32


def bucket_len(n: int, max_len: Optional[int] = None,
               bucket: int = PREFILL_BUCKET) -> int:
    """Sequence length ``n`` rounded up to a bucket multiple, capped at
    ``max_len`` (but never below ``n`` itself)."""
    b = -(-max(n, 1) // bucket) * bucket
    if max_len is not None:
        b = min(b, max(max_len, n))
    return b


@dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    generated: List[int] = field(default_factory=list)
    done: bool = False


class SchedulerStalled(RuntimeError):
    """``run_until_drained`` hit ``max_ticks`` with work still live.

    Carries the split so callers can account for both sides instead of
    silently receiving a partial drain: ``drained`` are the requests
    that did finish this drain, ``stranded`` the in-flight and queued
    requests left behind (still owned by the batcher — a later drain
    can finish them).
    """

    def __init__(self, max_ticks: int, drained: List[Request],
                 stranded: List[Request]):
        super().__init__(
            f"continuous batcher not drained after {max_ticks} ticks: "
            f"{len(drained)} finished, {len(stranded)} stranded")
        self.drained = drained
        self.stranded = stranded


class ContinuousBatcher:
    """Single-host scheduler over a fixed decode batch."""

    def __init__(self, params, cfg: ModelConfig, num_slots: int = 4,
                 max_len: int = 512, eos_id: int = 2):
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * num_slots
        self.cache = api.init_cache(cfg, num_slots, max_len)
        self.tokens = jnp.zeros((num_slots, 1), jnp.int32)
        self._step = jax.jit(make_serve_step(cfg),
                             donate_argnums=SERVE_STEP_DONATE)
        #: times the admission programs were traced (the jitted bodies
        #: run only when they trace); constant once every bucket is warm
        self.traces = 0
        self._prefill, self._splice = self._admission_programs()
        self._uid = 0
        self._ticks = 0
        self.finished: List[Request] = []
        # per-slot position bookkeeping (host side)
        self._slot_len = [0] * num_slots

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32) -> int:
        self._uid += 1
        self.queue.append(Request(self._uid, np.asarray(prompt, np.int32),
                                  max_new_tokens))
        return self._uid

    # -- internals ---------------------------------------------------------

    def _retire(self, req: Request) -> None:
        req.done = True
        self.finished.append(req)

    def _admit(self):
        """Fill empty slots: prefill each incoming prompt and splice its
        cache into the batch cache at the slot index. A request whose
        prefill-generated token already terminates it (EOS on the first
        token, or ``max_new_tokens`` reached) retires here instead of
        occupying a decode slot — the slot goes to the next queued
        request."""
        for slot in range(self.num_slots):
            if self.slots[slot] is not None:
                continue
            while self.queue:
                req = self.queue.popleft()
                if self._admit_one(req, slot):
                    break

    def _admission_programs(self):
        """The jitted prefill and splice of ``_admit_one``."""
        cfg, max_len, num_slots = self.cfg, self.max_len, self.num_slots

        def prefill(params, ids, true_len):
            self.traces += 1
            logits, cache1 = api.prefill(params, cfg, max_len, tokens=ids)
            tok = jnp.argmax(logits[0, true_len - 1]).astype(jnp.int32)
            return tok, cache1

        def splice(cache, cache1, tokens, slot, tok):
            self.traces += 1

            def put(path, batch_leaf, one_leaf):
                if batch_leaf.ndim == 0:
                    return batch_leaf
                # find the batch axis (with one slot, the first of size 1)
                for ax in range(batch_leaf.ndim):
                    if batch_leaf.shape[ax] == num_slots and \
                            one_leaf.shape[ax] == 1:
                        return jax.lax.dynamic_update_slice_in_dim(
                            batch_leaf, one_leaf.astype(batch_leaf.dtype),
                            slot, axis=ax)
                raise ValueError(
                    f"cannot splice cache leaf {jax.tree_util.keystr(path)}"
                    f": no axis of {batch_leaf.shape} holds {num_slots} "
                    f"slots where the request's {one_leaf.shape} holds 1")
            new_cache = jax.tree_util.tree_map_with_path(
                put, dict(cache), dict(cache1))
            new_cache["len"] = cache["len"]  # batch len: see step
            return new_cache, tokens.at[slot, 0].set(tok)

        # the splice donates the batch cache and the token column
        return jax.jit(prefill), jax.jit(splice, donate_argnums=(0, 2))

    def _admit_one(self, req: Request, slot: int) -> bool:
        """Prefill ``req`` and splice it into ``slot``; False when it
        retired at prefill and the slot is still free."""
        # right-pad to a bucketed length: one prefill trace per bucket
        # instead of one per distinct prompt length
        true_len = len(req.prompt)
        blen = bucket_len(true_len, self.max_len)
        with span("batcher.admit", uid=req.uid, prompt_len=true_len,
                  bucket=blen):
            ids = np.zeros((1, blen), np.int32)
            ids[0, :true_len] = req.prompt
            with span("batcher.prefill", traces=self.traces):
                tok, cache1 = self._prefill(self.params, ids,
                                            np.int32(true_len))
            with span("batcher.sync"):
                t = int(tok)
            req.generated.append(t)
            if t == self.eos_id or \
                    len(req.generated) >= req.max_new_tokens:
                # done at prefill: retire without touching the batch
                # cache and offer the slot to the next queued request
                self._retire(req)
                return False

            # splice single-sequence cache into the batch cache
            with span("batcher.splice"):
                self.cache, self.tokens = self._splice(
                    self.cache, cache1, self.tokens, np.int32(slot), tok)
            self.slots[slot] = req
            self._slot_len[slot] = true_len
            return True

    def _uniform_len(self) -> int:
        """The batch cache tracks one length; slots prefix-pad to align.
        We conservatively use the max active length."""
        return max(self._slot_len, default=0)

    def step(self) -> int:
        """One scheduler tick: admit, decode one token for every active
        slot, retire finished requests. Returns #active slots."""
        self._ticks += 1
        with jax.profiler.StepTraceAnnotation("batcher.tick",
                                              step_num=self._ticks):
            self._admit()
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if active:
                with span("batcher.decode", active=len(active),
                          slots=self.num_slots):
                    self._decode(active)
        return len(active)

    def _decode(self, active: List[int]) -> None:
        with span("batcher.step"):
            self.cache = {**self.cache,
                          "len": jnp.asarray(self._uniform_len(), jnp.int32)}
            tok, self.cache = self._step(self.params, self.tokens,
                                         self.cache)
        self.tokens = tok
        for i in active:
            self._slot_len[i] += 1
            req = self.slots[i]
            with span("batcher.sync"):
                t = int(tok[i, 0])
            req.generated.append(t)
            if t == self.eos_id or len(req.generated) >= req.max_new_tokens:
                self._retire(req)
                self.slots[i] = None
                self._slot_len[i] = 0

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        """Step until queue and slots are empty; drain and return the
        requests completed since the last drain (a persistent batcher —
        e.g. JaxBackend's per-model instance — can call this repeatedly
        without re-collecting or accumulating earlier batches).

        Raises :class:`SchedulerStalled` if ``max_ticks`` elapse with
        requests still queued or in flight — a silent partial drain
        would hand the caller an incomplete batch with no signal. The
        exception carries the drained/stranded split; stranded requests
        stay owned by the batcher, so a later (larger-budget) drain can
        still finish them."""
        ticks = 0
        while self.queue or any(r is not None for r in self.slots):
            if ticks >= max_ticks:
                done, self.finished = self.finished, []
                stranded = [r for r in self.slots if r is not None] \
                    + list(self.queue)
                raise SchedulerStalled(max_ticks, done, stranded)
            self.step()
            ticks += 1
        done, self.finished = self.finished, []
        return done
