"""ContinuousBatcher regressions: admit-time retirement, drain
stranding, prompt bucketing, jitted admission.

A stub model (scripted prefill logits + a ``tokens + 1`` decode step)
stands in for the real JAX models, so these tests pin the *scheduler's*
host-side bookkeeping without paying model compilation. Admission runs
jitted, so the stub's ``prefill`` runs once per trace: admissions are
counted from the ``batcher.admit`` spans, traces from ``traces``.

- a request whose prefill-generated first token is EOS (or whose
  ``max_new_tokens`` is 1) must retire at admit time instead of
  occupying a decode slot and appending tokens past EOS until the cap;
- ``run_until_drained`` hitting ``max_ticks`` must raise
  :class:`SchedulerStalled` with the drained/stranded split instead of
  silently returning a partial drain;
- admission traces its prefill once per bucket and its splice once, and
  the splice writes one slot's rows and nothing else.
"""

import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.serving import scheduler as sched  # noqa: E402
from repro.serving.scheduler import SchedulerStalled  # noqa: E402


class _StubApi:
    """Stands in for ``repro.models.api``: prefill emits logits peaked
    at a scripted first token; the cache is a trivial dict."""

    def __init__(self, first_token: int, vocab: int = 16):
        self.first_token = first_token
        self.vocab = vocab
        self.prefills = 0
        self.prefill_shapes = []

    def init_cache(self, cfg, num_slots, max_len):
        return {"len": jnp.asarray(0, jnp.int32)}

    def prefill(self, params, cfg, max_len, tokens):
        self.prefills += 1
        self.prefill_shapes.append(tuple(tokens.shape))
        # peak at every position: the scheduler buckets prompts and reads
        # the logits at the TRUE last prompt position, not at -1
        logits = np.zeros((1, tokens.shape[1], self.vocab), np.float32)
        logits[0, :, self.first_token] = 1.0
        return jnp.asarray(logits), {"len": jnp.asarray(0, jnp.int32)}


def _stub_step(cfg):
    # decode: next token = previous + 1 (never EOS for eos_id < first)
    def step(params, tokens, cache):
        return tokens + 1, cache
    return step


class _CacheStub(_StubApi):
    """A batch cache with a batch axis behind a layer axis, as the real
    stacked caches have; a prefill fills its one row with ``sum(ids)``."""

    def init_cache(self, cfg, num_slots, max_len):
        return {"len": jnp.asarray(0, jnp.int32),
                "rows": jnp.zeros((2, num_slots, 4), jnp.float32)}

    def prefill(self, params, cfg, max_len, tokens):
        logits, _ = super().prefill(params, cfg, max_len, tokens)
        row = jnp.full((2, 1, 4), tokens.sum(), jnp.float32)
        return logits, {"len": jnp.asarray(0, jnp.int32), "rows": row}


def _batcher(monkeypatch, first_token, *, eos_id=2, num_slots=2,
             stub=None):
    stub = stub or _StubApi(first_token)
    monkeypatch.setattr(sched, "api", stub)
    monkeypatch.setattr(sched, "make_serve_step", _stub_step)
    return sched.ContinuousBatcher(None, None, num_slots=num_slots,
                                   max_len=32, eos_id=eos_id), stub


def _record_spans(monkeypatch):
    """Replace the batcher's profiler span with a recorder of
    ``(name, stats)``."""
    spans = []

    def record(name, **stats):
        spans.append((name, stats))
        return contextlib.nullcontext()
    monkeypatch.setattr(sched, "span", record)
    return spans


def _admits(spans):
    return sum(name == "batcher.admit" for name, _ in spans)


def test_eos_on_prefill_retires_at_admit(monkeypatch):
    """Regression: a request whose FIRST generated token is EOS used to
    occupy a decode slot and keep appending tokens until max_new_tokens;
    it must retire at admit time with exactly the one token."""
    spans = _record_spans(monkeypatch)
    b, _ = _batcher(monkeypatch, first_token=2, eos_id=2)
    for _ in range(3):
        b.submit(np.arange(4), max_new_tokens=8)
    # one tick admits (and retires) everything: no decode step needed
    assert b.step() == 0
    assert all(s is None for s in b.slots)
    done = b.run_until_drained()
    assert len(done) == 3
    for r in done:
        assert r.done and r.generated == [2]
    assert _admits(spans) == 3


def test_max_new_tokens_one_retires_at_admit(monkeypatch):
    b, _ = _batcher(monkeypatch, first_token=5, eos_id=2)
    b.submit(np.arange(3), max_new_tokens=1)
    done = b.run_until_drained()
    assert len(done) == 1
    assert done[0].generated == [5]


def test_retired_admit_frees_slot_for_next_request(monkeypatch):
    """Admit-time retirement must offer the slot to the next queued
    request in the same tick — 5 instant-EOS requests drain through 2
    slots in one step."""
    b, _ = _batcher(monkeypatch, first_token=2, eos_id=2, num_slots=2)
    for _ in range(5):
        b.submit(np.arange(4), max_new_tokens=4)
    assert b.step() == 0
    assert len(b.finished) == 5 and not b.queue


def test_normal_decode_still_stops_at_eos_and_cap(monkeypatch):
    """Non-degenerate requests keep the existing step-time semantics:
    decode until the cap (the stub never emits EOS mid-decode)."""
    b, _ = _batcher(monkeypatch, first_token=5, eos_id=2)
    b.submit(np.arange(4), max_new_tokens=3)
    done = b.run_until_drained()
    assert len(done) == 1
    assert done[0].generated == [5, 6, 7]  # tokens+1 per step, cap at 3


def test_run_until_drained_raises_on_stall(monkeypatch):
    """Regression: hitting max_ticks used to silently return a partial
    drain; callers must get the drained/stranded split instead."""
    b, _ = _batcher(monkeypatch, first_token=5, eos_id=2)
    b.submit(np.arange(4), max_new_tokens=1)    # retires at admit
    b.submit(np.arange(4), max_new_tokens=10)   # needs 9 decode ticks
    with pytest.raises(SchedulerStalled) as ei:
        b.run_until_drained(max_ticks=3)
    err = ei.value
    assert [r.generated for r in err.drained] == [[5]]
    assert len(err.stranded) == 1 and not err.stranded[0].done
    # the stranded request stays owned by the batcher: a later drain
    # with budget finishes it
    done = b.run_until_drained()
    assert len(done) == 1 and len(done[0].generated) == 10


def test_prefill_prompts_are_bucketed(monkeypatch):
    """Distinct prompt lengths collapse onto PREFILL_BUCKET multiples:
    the prefill jit site sees a bounded shape census instead of one
    retrace per length."""
    spans = _record_spans(monkeypatch)
    b, stub = _batcher(monkeypatch, first_token=5, eos_id=2, num_slots=2)
    for n in (1, 3, 7, 17, 31, 32):
        b.submit(np.arange(n), max_new_tokens=1)
    b.run_until_drained()
    assert _admits(spans) == 6
    assert {s[1] for s in stub.prefill_shapes} == {32}
    assert [st["bucket"] for name, st in spans
            if name == "batcher.admit"] == [32] * 6


def test_bucket_len_caps_at_max_len():
    assert sched.bucket_len(1) == sched.PREFILL_BUCKET
    assert sched.bucket_len(32) == 32
    assert sched.bucket_len(33) == 64
    assert sched.bucket_len(40, max_len=48) == 48   # capped
    assert sched.bucket_len(50, max_len=48) == 50   # never below n


def test_bucketed_prefill_reads_true_last_position(monkeypatch):
    """The admitted first token must come from the logits at the true
    prompt end, not the padded end — a stub peaking ONLY at position
    true_len-1 proves the read index."""

    class _PositionStub(_StubApi):
        def prefill(self, params, cfg, max_len, tokens):
            self.prefills += 1
            logits = np.zeros((1, tokens.shape[1], self.vocab), np.float32)
            logits[0, 4, self.first_token] = 1.0  # true_len=5 -> index 4
            return jnp.asarray(logits), {"len": jnp.asarray(0, jnp.int32)}

    b, _ = _batcher(monkeypatch, first_token=7, eos_id=2,
                    stub=_PositionStub(7))
    b.submit(np.arange(5), max_new_tokens=1)
    (r,) = b.run_until_drained()
    assert r.generated == [7]


def test_one_bucket_traces_prefill_once(monkeypatch):
    """Three admissions of one bucket, at three true lengths, run one
    prefill program: the true length is traced, not baked in."""
    spans = _record_spans(monkeypatch)
    b, stub = _batcher(monkeypatch, first_token=5, eos_id=2)
    for n in (3, 17, 32):
        b.submit(np.arange(n), max_new_tokens=1)   # retire at prefill
    done = b.run_until_drained()
    assert [r.generated for r in done] == [[5]] * 3
    assert _admits(spans) == 3
    assert stub.prefills == 1 and b.traces == 1
    # the stat reads the count when its span opens
    assert [st["traces"] for name, st in spans
            if name == "batcher.prefill"] == [0, 1, 1]


def test_every_slot_admitted_traces_splice_once(monkeypatch):
    b, _ = _batcher(monkeypatch, first_token=5, eos_id=2, num_slots=4,
                    stub=_CacheStub(5))
    for _ in range(4):
        b.submit(np.arange(4), max_new_tokens=3)
    assert b.step() == 4
    assert all(r is not None for r in b.slots)
    assert b.traces == 2            # one prefill, one splice
    b.submit(np.arange(4), max_new_tokens=3)
    b.run_until_drained()
    assert b.traces == 2


def test_retired_at_prefill_leaves_batch_cache_untouched(monkeypatch):
    b, _ = _batcher(monkeypatch, first_token=5, eos_id=2,
                    stub=_CacheStub(5))
    b.cache = {**b.cache, "rows": jnp.arange(16, dtype=jnp.float32
                                             ).reshape(2, 2, 4)}
    rows, tokens = np.asarray(b.cache["rows"]), np.asarray(b.tokens)
    cache_obj = b.cache
    b.submit(np.arange(4), max_new_tokens=1)
    assert b.step() == 0
    assert b.cache is cache_obj
    np.testing.assert_array_equal(np.asarray(b.cache["rows"]), rows)
    np.testing.assert_array_equal(np.asarray(b.tokens), tokens)


@pytest.mark.parametrize("slot", [0, 2, 3])
def test_splice_writes_only_its_slot(monkeypatch, slot):
    """The splice into slot k writes row k of every leaf and the token
    column's entry k; every other slot stays bit-identical."""
    b, _ = _batcher(monkeypatch, first_token=5, eos_id=2, num_slots=4,
                    stub=_CacheStub(5))
    before = np.random.default_rng(slot).standard_normal(
        (2, 4, 4)).astype(np.float32)
    b.cache = {**b.cache, "rows": jnp.asarray(before)}
    b.tokens = jnp.arange(4, dtype=jnp.int32)[:, None] + 10
    b.slots = [object() if i != slot else None for i in range(4)]
    b.submit(np.arange(4), max_new_tokens=3)
    b._admit()
    assert b.slots[slot].uid == 1
    after = np.asarray(b.cache["rows"])
    others = [i for i in range(4) if i != slot]
    np.testing.assert_array_equal(after[:, others], before[:, others])
    np.testing.assert_array_equal(after[:, slot], np.full((2, 4), 6.0))
    np.testing.assert_array_equal(
        np.asarray(b.tokens)[:, 0],
        [5 if i == slot else 10 + i for i in range(4)])
    assert int(b.cache["len"]) == 0    # the batch length is carried
