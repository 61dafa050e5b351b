"""Compile-path static analyzer: seeded-defect + zoo-clean suite.

Each diagnostic code gets a hostile input proving it fires with the
right code/site, and the in-tree zoo is asserted clean — the analyzer is
a CI gate, so both directions (catches real defects, no false alarms on
shipping configs) are load-bearing.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.analysis.compiled import (  # noqa: E402
    DTYPE_UPCAST, HOST_TRANSFER, LOOP_TRANSFER, NON_DONATED_BUFFER,
    PALLAS_BLOCK_SHAPE, PALLAS_VMEM, RECOMPILE_RISK, CompiledAnalysisError,
    CompiledReport, audit_kernel, audit_kernels, audit_model, check_donation,
    check_dtype_upcast, check_serving_recompile, check_tpu_tiling,
    check_transfers, merge_reports, parse_declared_donors, parse_io_aliases)
from repro.configs import get_config  # noqa: E402

# -- transfer lint (synthetic HLO) -----------------------------------------

_HOT_LOOP_COPY_HLO = """
HloModule test

%body.1 (p: (s32[], f32[512,1024])) -> (s32[], f32[512,1024]) {
  %p = (s32[], f32[512,1024]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[512,1024]{1,0} get-tuple-element(%p), index=1
  %cp = f32[512,1024]{1,0} copy(%g1)
  %c1 = s32[] constant(1)
  %add = s32[] add(%g0, %c1)
  ROOT %t = (s32[], f32[512,1024]) tuple(%add, %cp)
}

%cond.1 (p: (s32[], f32[512,1024])) -> pred[] {
  %p = (s32[], f32[512,1024]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%g0, %c), direction=LT
}

ENTRY %main (x: f32[512,1024]) -> f32[512,1024] {
  %x = f32[512,1024]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[512,1024]) tuple(%c0, %x)
  %w = (s32[], f32[512,1024]) while(%t0), condition=%cond.1, body=%body.1
  ROOT %out = f32[512,1024]{1,0} get-tuple-element(%w), index=1
}
"""


def test_loop_transfer_fires_on_hot_loop_copy():
    diags = check_transfers(_HOT_LOOP_COPY_HLO, subject="t", site="s")
    assert [d.code for d in diags] == [LOOP_TRANSFER]
    d = diags[0]
    assert d.severity == "warning"
    assert d.data["multiplier"] == 7.0
    assert d.data["bytes"] == 512 * 1024 * 4


def test_loop_transfer_ignores_small_and_cold_copies():
    # same copy outside any loop: multiplier 1 -> not flagged
    hlo = """
HloModule test

ENTRY %main (x: f32[512,1024]) -> f32[512,1024] {
  %x = f32[512,1024]{1,0} parameter(0)
  ROOT %cp = f32[512,1024]{1,0} copy(%x)
}
"""
    assert check_transfers(hlo, subject="t", site="s") == []


def test_host_transfer_fires_on_outfeed():
    hlo = """
HloModule test

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %tok = token[] after-all()
  %of = token[] outfeed(%x, %tok)
  ROOT %cp = f32[8,8]{1,0} copy(%x)
}
"""
    diags = check_transfers(hlo, subject="t", site="s")
    assert [d.code for d in diags] == [HOST_TRANSFER]
    assert diags[0].severity == "error"
    assert diags[0].data["opcode"] == "outfeed"


# -- donation lint (real lowerings) ----------------------------------------


def _carry_step(tok, cache):
    return tok + 1, cache + 1.0


_TOK = jax.ShapeDtypeStruct((2, 1), jnp.int32)
_CACHE = jax.ShapeDtypeStruct((512, 512), jnp.float32)  # 1 MiB carried


def test_non_donated_buffer_fires_without_donation():
    text = jax.jit(_carry_step).lower(_TOK, _CACHE).compile().as_text()
    diags = check_donation(text, subject="t", site="s")
    assert [d.code for d in diags] == [NON_DONATED_BUFFER]
    d = diags[0]
    assert d.severity == "error"
    assert d.data["wasted_bytes"] == 512 * 512 * 4
    # the tiny token buffer is not an offender
    assert all(o["bytes"] >= 4096 for o in d.data["offenders"])


def test_donation_lint_clean_with_donate_argnums():
    lowered = jax.jit(_carry_step, donate_argnums=(1,)).lower(_TOK, _CACHE)
    text = lowered.compile().as_text()
    # CPU XLA drops the alias from the optimized module, so the lint
    # accepts the declared donation from the lowered StableHLO
    diags = check_donation(text, subject="t", site="s",
                           lowered_text=lowered.as_text())
    assert diags == []
    assert parse_declared_donors(lowered.as_text()) == {1}


def test_parse_io_aliases_synthetic():
    header = ("HloModule m, input_output_alias={ {0}: (2, {}, may-alias), "
              "{1}: (0, {}, must-alias) }, entry_computation_layout=...")
    assert parse_io_aliases(header) == {0, 2}
    assert parse_io_aliases("HloModule m") == set()


# -- dtype-upcast lint ------------------------------------------------------

_W = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
_X32 = jax.ShapeDtypeStruct((8, 64), jnp.float32)
_XBF = jax.ShapeDtypeStruct((8, 64), jnp.bfloat16)


def test_dtype_upcast_fires_on_poisoned_matmul_path():
    def poisoned(w, x):
        # a forgotten astype(bf16): every dot runs in f32
        y = x @ w.astype(jnp.float32)
        return y @ w.astype(jnp.float32)

    diags = check_dtype_upcast(poisoned, _W, _X32, subject="t", site="s")
    assert [d.code for d in diags] == [DTYPE_UPCAST]
    assert diags[0].data["f32_share"] == 1.0
    assert diags[0].data["top_f32_dots"]


def test_dtype_upcast_clean_on_bf16_path_and_f32_models():
    def clean(w, x):
        return (x @ w) @ w

    assert check_dtype_upcast(clean, _W, _XBF, subject="t", site="s") == []

    def all_f32(w, x):
        return x @ w.astype(jnp.float32)

    # f32-native models are exempt: everything being f32 is not a defect
    assert check_dtype_upcast(all_f32, _W, _X32, subject="t", site="s",
                              model_dtype="float32") == []


def test_dtype_upcast_tolerates_small_f32_island():
    def island(w, x):
        main = (x @ w) @ w                       # bf16 main path
        router = x.astype(jnp.float32)[:, :8] @ \
            w.astype(jnp.float32)[:8, :8]        # tiny f32 island
        return main, router

    assert check_dtype_upcast(island, _W, _XBF, subject="t", site="s") == []


# -- Pallas resource lint ---------------------------------------------------


def test_pallas_block_shape_heads_not_divisible():
    diags = audit_kernel("flash_attention", "t",
                         b=1, s=64, h=5, kh=2, hd=64)
    assert [d.code for d in diags] == [PALLAS_BLOCK_SHAPE]
    assert "heads" in diags[0].message


def test_pallas_block_shape_ssd_ragged_seq():
    diags = audit_kernel("ssd_scan", "t",
                         b=1, s=100, h=4, g=2, p=64, n=16, chunk=32)
    assert [d.code for d in diags] == [PALLAS_BLOCK_SHAPE]
    assert "seq" in diags[0].message and "ragged" in diags[0].message


def test_pallas_block_shape_nonpositive_block():
    diags = audit_kernel("moe_ffn", "t",
                         g=1, e=4, c=64, d=64, f=128, block_c=0)
    assert PALLAS_BLOCK_SHAPE in [d.code for d in diags]
    assert "positive" in diags[0].message


def test_pallas_vmem_fires_on_oversized_tiles():
    diags = audit_kernel("flash_attention", "t",
                         b=1, s=8192, h=4, kh=4, hd=256,
                         block_q=4096, block_k=4096)
    assert [d.code for d in diags] == [PALLAS_VMEM]
    assert diags[0].data["working_set_bytes"] > diags[0].data["budget_bytes"]


def test_pallas_vmem_budget_override():
    # a shape that fits 16 MiB fails a 64 KiB budget
    diags = audit_kernel("flash_decode", "t",
                         b=1, s=512, h=4, kh=2, hd=64, block_s=128,
                         vmem_bytes=64 * 1024)
    assert [d.code for d in diags] == [PALLAS_VMEM]


def test_pallas_block_shape_tpu_tiling_rule():
    # flash_decode's old K/V tile on a (B, S, K, Hd) cache at llama3.2-1b
    # decode widths: the chip's compiler refused its (1, 128) last dims
    diags = check_tpu_tiling("t", "flash_decode", "k",
                             (1, 112, 1, 128), (4, 112, 8, 128))
    assert [d.code for d in diags] == [PALLAS_BLOCK_SHAPE]
    assert "tiling" in diags[0].message
    # the (B, K, S, Hd) layout the kernel reads now passes
    assert check_tpu_tiling("t", "flash_decode", "k",
                            (1, 1, 112, 128), (4, 8, 112, 128)) == []


def test_audit_kernel_unknown_name_raises():
    with pytest.raises(KeyError):
        audit_kernel("nonexistent", "t")


# -- recompile-risk lint ----------------------------------------------------


def test_recompile_risk_fires_without_bucketing():
    cfg = get_config("llama3.2-1b", reduced=True)
    diags = check_serving_recompile(
        cfg, subject="t", bucket_fn=lambda n, max_len: n)  # identity: no buckets
    assert [d.code for d in diags] == [RECOMPILE_RISK]
    assert diags[0].site == "scheduler.prefill"
    assert diags[0].data["distinct_shapes"] == 96


def test_recompile_risk_clean_with_scheduler_bucketing():
    cfg = get_config("llama3.2-1b", reduced=True)
    assert check_serving_recompile(cfg, subject="t") == []


def test_recompile_risk_fires_on_uncached_jit_closure(monkeypatch):
    from repro.serving import decode as dec
    cfg = get_config("llama3.2-1b", reduced=True)
    monkeypatch.setattr(
        dec, "serve_step_jit",
        lambda cfg, temperature=0.0: jax.jit(
            dec.make_serve_step(cfg, temperature)))
    diags = check_serving_recompile(cfg, subject="t")
    assert [d.code for d in diags] == [RECOMPILE_RISK]
    assert diags[0].site == "decode.serve_step"


# -- report plumbing --------------------------------------------------------


def test_report_strict_gate_raises():
    rep = CompiledReport("t")
    rep.extend(check_transfers(_HOT_LOOP_COPY_HLO, subject="t", site="s"))
    assert rep.ok and not rep.clean  # warnings only
    rep.raise_for_errors()           # warnings pass the default gate
    with pytest.raises(CompiledAnalysisError):
        rep.raise_for_errors(warnings_fatal=True)
    merged = merge_reports("m", [rep, None, CompiledReport("x")])
    assert merged.codes() == [LOOP_TRANSFER]
    d = rep.to_dict()
    assert d["warnings"] == 1 and d["diagnostics"][0]["code"] == LOOP_TRANSFER


# -- the shipping zoo and kernel cases are clean ----------------------------


def test_zoo_arch_audit_clean_full():
    rep = audit_model("llama3.2-1b", compile=True)
    assert rep.clean, rep.format()
    assert rep.analyze_s > 0


def test_default_kernel_cases_clean():
    reports = audit_kernels()
    assert len(reports) >= 7
    for rep in reports:
        assert rep.clean, rep.format()
