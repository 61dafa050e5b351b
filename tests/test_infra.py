"""Data pipeline, checkpointing, serving scheduler, HLO analysis, cost
catalog."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # optional dep (requirements-dev.txt): stub the decorators so only the
    # property-based tests skip — the rest of this module still runs
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def settings(*a, **k):
        return lambda f: f

    def given(*a, **k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

from repro.configs import ARCHS, get_config


# -- data --------------------------------------------------------------------


def test_loader_deterministic_and_resumable():
    from repro.data.loader import LMBatchLoader
    cfg = get_config("llama3.2-1b", reduced=True)
    l1 = LMBatchLoader(cfg, 4, 32, seed=1)
    l2 = LMBatchLoader(cfg, 4, 32, seed=1)
    for step in (0, 5, 17):
        b1, b2 = l1.batch_at(step), l2.batch_at(step)
        assert np.array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(l1.batch_at(0)["tokens"],
                              l1.batch_at(1)["tokens"])


@settings(max_examples=25, deadline=None)
@given(st.text(max_size=200))
def test_byte_tokenizer_roundtrip(text):
    from repro.data.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(text, add_bos=False)) == text


@settings(max_examples=25, deadline=None)
@given(st.text(min_size=1, max_size=200), st.integers(100, 50_000))
def test_hash_tokenizer_in_vocab(text, vocab):
    from repro.data.tokenizer import HashWordTokenizer
    tok = HashWordTokenizer(vocab)
    ids = tok.encode(text)
    assert all(0 <= i < vocab for i in ids)
    assert tok.encode(text) == ids  # deterministic


# -- checkpoint / fault tolerance ----------------------------------------------


def test_checkpoint_atomicity_and_gc():
    from repro.checkpoint.manager import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep_last=2)
        tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3)}
        for step in (1, 2, 3):
            mgr.save(step, {"params": tree}, {"k": step})
        assert mgr.steps() == [2, 3]  # keep_last gc
        # torn write is invisible (no COMMITTED marker)
        os.makedirs(os.path.join(d, "step_00000009"))
        assert mgr.latest_step() == 3
        trees, meta = mgr.load()
        assert meta["k"] == 3


def test_train_resume_bitexact():
    from repro.launch.train import train
    with tempfile.TemporaryDirectory() as d:
        p_full, o_full, hist_full, _ = train(
            "llama3.2-1b", steps=8, global_batch=4, seq_len=32,
            ckpt_dir=None)
        train("llama3.2-1b", steps=4, global_batch=4, seq_len=32,
              ckpt_dir=d, ckpt_every=4)
        p_res, o_res, hist_res, _ = train(
            "llama3.2-1b", steps=8, global_batch=4, seq_len=32,
            ckpt_dir=d, ckpt_every=100)
        for a, b in zip(jax.tree_util.tree_leaves(p_full),
                        jax.tree_util.tree_leaves(p_res)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-5)


def test_elastic_reshard_roundtrip():
    from repro.checkpoint.elastic import reshard
    x = {"w": np.arange(16, dtype=np.float32).reshape(4, 4)}
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    y = reshard(x, sharding)
    np.testing.assert_array_equal(np.asarray(y["w"]), x["w"])


def test_straggler_watchdog():
    from repro.launch.train import StragglerWatchdog
    wd = StragglerWatchdog(factor=3.0, warmup=3)
    for i in range(5):
        assert not wd.observe(i, 1.0)
    assert wd.observe(5, 10.0)
    assert wd.flagged


# -- serving --------------------------------------------------------------------


def test_continuous_batcher_drains():
    # end-to-end serving demo: PipelineServer admission/micro-batching
    # over JaxBackend, whose chunks drain through the continuous batcher
    from repro.launch.serve import serve_demo
    tickets, report = serve_demo("llama3.2-1b", requests=5, slots=2,
                                 max_new=6, verbose=False)
    assert len(tickets) == 5
    assert report["completed"] == 5 and report["failed"] == 0
    assert all(tk.error is None and tk.docs for tk in tickets)
    assert report["out_tokens"] > 0 and report["batches"] >= 1


def test_serve_cli_exits_nonzero_when_a_request_fails(monkeypatch):
    # a backend whose every submit raises: PipelineServer resolves each
    # ticket with the error, and the CLI must not exit 0 over them
    from repro.engine import backend as be
    from repro.launch import serve

    class DeviceLost(be.SimBackend):
        def __init__(self, seed=0, **_):
            super().__init__(seed=seed)

        def submit(self, requests):
            raise RuntimeError("device lost")

    monkeypatch.setattr(be, "JaxBackend", DeviceLost)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    monkeypatch.setattr("sys.argv", ["serve", "--requests", "2"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 1


@pytest.mark.parametrize("mismatch", [{"max_new_tokens": 4},
                                      {"decode_slots": 2}, {"seed": 1},
                                      {"reduced": False}])
def test_serve_demo_refuses_a_backend_built_otherwise(mismatch):
    # a passed backend must serve the configuration the demo reports
    from repro.engine.backend import JaxBackend
    from repro.launch.serve import serve_demo
    kw = dict({"seed": 0, "max_new_tokens": 8, "decode_slots": 4}, **mismatch)
    backend = JaxBackend(**kw)
    with pytest.raises(ValueError, match="backend was built with"):
        serve_demo("llama3.2-1b", requests=1, slots=4, max_new=8, seed=0,
                   backend=backend, verbose=False)


def test_cache_bytes_matches_measured():
    from repro.serving.kv_cache import cache_bytes, make_cache, \
        measured_cache_bytes
    for arch in ("llama3.2-1b", "gemma3-27b", "mamba2-370m", "zamba2-2.7b",
                 "whisper-medium"):
        cfg = get_config(arch, reduced=True)
        cache = make_cache(cfg, batch=2, max_len=64)
        est = cache_bytes(cfg, 2, 64)
        got = measured_cache_bytes(cache)
        # estimate within 25% (scalar len + rounding slack)
        assert abs(est - got) / got < 0.25, (arch, est, got)


# -- HLO analysis ----------------------------------------------------------------


def test_hlo_trip_count_weighting():
    from repro.analysis.compiled.hlo_analysis import analyze

    def f(w, x):
        def outer(x, _):
            def body(x, w):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(body, x, w, length=w.shape[0])
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=3)
        return x

    w = jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    txt = jax.jit(f).lower(w, x).compile().as_text()
    costs = analyze(txt)
    expected = 3 * 5 * 2 * 8 * 32 * 32
    assert abs(costs.flops - expected) / expected < 0.05


def test_hlo_collective_parsing_synthetic():
    from repro.analysis.compiled.hlo_analysis import analyze
    hlo = """
HloModule test

%body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,16]{1,0} all-reduce(%g1), replica_groups={}
  %c1 = s32[] constant(1)
  %add = s32[] add(%g0, %c1)
  ROOT %t = (s32[], f32[8,16]) tuple(%add, %ar)
}

%cond.1 (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%g0, %c), direction=LT
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[8,16]) tuple(%c0, %x)
  %w = (s32[], f32[8,16]) while(%t0), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w), index=1
}
"""
    costs = analyze(hlo)
    assert costs.collective_counts.get("all-reduce") == 7.0
    assert costs.collective_bytes["all-reduce"] == 7 * 8 * 16 * 4


# -- model catalog / pricing ---------------------------------------------------------


def test_catalog_prices_scale_with_size():
    from repro.core.models_catalog import analytic_price, catalog
    cards = catalog()
    assert set(cards) == set(ARCHS)
    small = analytic_price("llama3.2-1b")
    big = analytic_price("grok-1-314b")
    assert big["in"] > small["in"] * 10
    for c in cards.values():
        assert c.price_in > 0 and c.price_out > 0
