"""Direct unit tests for the HLO parser primitives.

``test_infra.py`` exercises ``analyze`` end-to-end over real lowerings;
these tests pin the primitives the compiled-analysis lints build on:
trip-count extraction (nested while, zero-trip, dynamic-bound fallback),
the dtype byte table (sub-byte s4/u4, f8 variants), and the
``HLOParseError`` raised on unknown dtypes instead of a silent skip.
"""

import pytest

from repro.analysis.compiled.hlo_analysis import (
    HLOParseError, _trip_count, _type_bytes, analyze, compute_multipliers,
    parse_computations)

_NESTED_WHILE_HLO = """
HloModule test

%inner_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8]{0} get-tuple-element(%p), index=1
  %c1 = s32[] constant(1)
  %add = s32[] add(%g0, %c1)
  ROOT %t = (s32[], f32[8]) tuple(%add, %g1)
}

%inner_cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%g0, %c), direction=LT
}

%outer_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8]{0} get-tuple-element(%p), index=1
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[8]) tuple(%c0, %g1)
  %w = (s32[], f32[8]) while(%t0), condition=%inner_cond, body=%inner_body
  %g2 = f32[8]{0} get-tuple-element(%w), index=1
  %c1 = s32[] constant(1)
  %add = s32[] add(%g0, %c1)
  ROOT %t = (s32[], f32[8]) tuple(%add, %g2)
}

%outer_cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(3)
  ROOT %lt = pred[] compare(%g0, %c), direction=LT
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[8]) tuple(%c0, %x)
  %w = (s32[], f32[8]) while(%t0), condition=%outer_cond, body=%outer_body
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""


def test_trip_count_nested_while_multiplies():
    comps = parse_computations(_NESTED_WHILE_HLO)
    assert _trip_count(comps["outer_cond"]) == 3
    assert _trip_count(comps["inner_cond"]) == 5
    mult = compute_multipliers(comps)
    assert mult["outer_body"] == 3.0
    assert mult["inner_body"] == 15.0  # 3 outer trips x 5 inner trips


def test_trip_count_zero_trip_loop():
    hlo = """
%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(0)
  ROOT %lt = pred[] compare(%g0, %c), direction=LT
}
"""
    comps = parse_computations(hlo)
    # constant(0) bound means the body never runs: 0, not the old
    # best-of-1 fallback
    assert _trip_count(comps["cond"]) == 0


def test_trip_count_dynamic_bound_falls_back_to_one():
    hlo = """
%cond (p: (s32[], s32[])) -> pred[] {
  %p = (s32[], s32[]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = s32[] get-tuple-element(%p), index=1
  ROOT %lt = pred[] compare(%g0, %g1), direction=LT
}
"""
    comps = parse_computations(hlo)
    assert _trip_count(comps["cond"]) == 1


# -- dtype byte table -------------------------------------------------------


def test_type_bytes_sub_byte_and_f8_dtypes():
    assert _type_bytes("s4[16]") == 8
    assert _type_bytes("u4[16]") == 8
    assert _type_bytes("s4[4]") == 2
    assert _type_bytes("f8e5m2fnuz[10]") == 10
    assert _type_bytes("f8e4m3fnuz[10]") == 10
    assert _type_bytes("f8e8m0fnu[10]") == 10
    assert _type_bytes("bf16[2,3]") == 12
    # tuple types sum their element arrays
    assert _type_bytes("(s4[16], f32[2])") == 8 + 8


def test_type_bytes_ignores_non_array_tokens():
    assert _type_bytes("token[]") == 0
    assert _type_bytes("(f32[4], token[])") == 16


def test_unknown_dtype_raises_named_error_with_line():
    line = "%x = q3[8]{0} custom-call(%y)"
    with pytest.raises(HLOParseError) as ei:
        _type_bytes("q3[8]", line)
    err = ei.value
    assert err.dtype == "q3"
    assert line in str(err) or "q3" in str(err)
    assert err.line == line


def test_analyze_surfaces_parse_error_instead_of_undercounting():
    hlo = """
HloModule test

ENTRY %main (x: q3[64,64]) -> q3[64,64] {
  %x = q3[64,64]{1,0} parameter(0)
  ROOT %cp = q3[64,64]{1,0} copy(%x)
}
"""
    with pytest.raises(HLOParseError) as ei:
        analyze(hlo)
    assert ei.value.dtype == "q3"
    assert "copy" in ei.value.line
