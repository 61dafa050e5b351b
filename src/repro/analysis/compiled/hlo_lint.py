"""Compiled-HLO lint: hot-loop transfers and missing buffer donation.

Both checks walk optimized HLO text through the ``hlo_analysis`` parser,
so trip-weighted "is this inside the hot loop" reasoning reuses its
call-graph/multiplier machinery.

Transfer lint
  ``host-transfer`` (error): infeed/outfeed/send/recv (or host-annotated
  custom calls) anywhere reachable from ENTRY — the serving step must
  never bounce through the host.
  ``loop-transfer`` (warning): a ``copy`` at least ``MIN_LOOP_COPY_BYTES``
  large inside a computation whose execution multiplier is > 1 (i.e. a
  while/scan body) — per-step traffic that scales with trip count.
  Dtype-widening copies (bf16->f32 with identical dims) are skipped:
  they are a CPU-backend artifact of emulated bf16 dots, exactly as in
  ``hlo_analysis.analyze``.

Donation lint
  ``non-donated-buffer`` (error): an entry parameter whose shape+dtype
  also appears among the outputs (the signature of carried state — KV
  caches, decode tokens) but is not covered by ``input_output_alias``.
  XLA then keeps both generations of the buffer live: peak HBM for the
  cache doubles. Buffers under ``MIN_DONATION_BYTES`` are ignored
  (scalars and per-step token ids are noise, not memory).
"""

from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

from repro.analysis.compiled.diagnostics import (
    HOST_TRANSFER, LOOP_TRANSFER, NON_DONATED_BUFFER, SEV_ERROR, SEV_WARNING,
    CompiledDiagnostic, diag)
from repro.analysis.compiled.hlo_analysis import (
    _SHAPE_RE, _shape_dims, _type_bytes, compute_multipliers,
    parse_computations)

#: copies smaller than this inside a hot loop are register/layout noise
MIN_LOOP_COPY_BYTES = 1 << 20
#: undonated carried buffers smaller than this are not a memory problem
MIN_DONATION_BYTES = 4096

_HOST_OPS = {"infeed", "outfeed", "send", "send-done", "recv", "recv-done"}
_HOST_CUSTOM_CALL = re.compile(
    r"custom_call_target=\"[^\"]*(MoveToHost|MoveToDevice|HostTransfer)")

_ALIAS_ENTRY = re.compile(r"\{[\d,\s]*\}\s*:\s*\((\d+)")
_PARAM_NUM = re.compile(r"parameter\((\d+)\)")
#: StableHLO donation marker: ``%argN: <type> {tf.aliasing_output = K}``.
#: CPU XLA drops donation at compile time (no input_output_alias in the
#: optimized module), so the lint also honours the *declared* donation in
#: the lowered text — arg numbering matches entry parameter numbering.
#: ``[^,()]*`` keeps the match inside one argument: commas/parens separate
#: args, so the marker can't be attributed to an earlier %arg.
_STABLEHLO_DONOR = re.compile(
    r"%arg(\d+)[^,()]*\{[^}]*(?:tf\.aliasing_output|jax\.buffer_donor)")


def _is_widening_copy(op, comp) -> bool:
    if not op.operands:
        return False
    in_type = comp.symbols.get(op.operands[0], "")
    return (_shape_dims(in_type) == _shape_dims(op.type_str)
            and _type_bytes(in_type) != _type_bytes(op.type_str))


def check_transfers(hlo_text: str, *, subject: str, site: str,
                    min_loop_copy_bytes: int = MIN_LOOP_COPY_BYTES
                    ) -> List[CompiledDiagnostic]:
    comps = parse_computations(hlo_text)
    mult = compute_multipliers(comps)
    out: List[CompiledDiagnostic] = []
    for name, comp in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 0.0)
        if m <= 0.0:
            continue
        for op in comp.ops:
            if op.opcode in _HOST_OPS or (
                    op.opcode == "custom-call"
                    and _HOST_CUSTOM_CALL.search(op.line)):
                out.append(diag(
                    HOST_TRANSFER, SEV_ERROR, subject, site,
                    f"host transfer {op.opcode!r} ({op.name}) reachable "
                    f"from ENTRY (executes ~{m:.0f}x per step)",
                    opcode=op.opcode, op=op.name, multiplier=m,
                    computation=name))
                continue
            if op.opcode != "copy" or m <= 1.0:
                continue
            nbytes = _type_bytes(op.type_str, op.line)
            if nbytes < min_loop_copy_bytes:
                continue
            if _is_widening_copy(op, comp):
                continue  # CPU bf16-emulation artifact, not real traffic
            out.append(diag(
                LOOP_TRANSFER, SEV_WARNING, subject, site,
                f"{nbytes / 2**20:.1f} MiB copy ({op.name}) inside hot "
                f"computation {name!r} (multiplier {m:.0f}x): "
                f"{nbytes * m / 2**20:.0f} MiB of per-step loop traffic",
                op=op.name, bytes=nbytes, multiplier=m, computation=name))
    return out


def parse_io_aliases(hlo_text: str) -> Set[int]:
    """Parameter numbers covered by the module's ``input_output_alias``.

    Entries nest braces (``{ {0}: (2, {}, may-alias), ... }``), so the
    block is delimited with a depth counter rather than a regex — a lazy
    ``\\{(.*?)\\}`` would stop at the first inner ``}`` and drop every
    entry after the first.
    """
    key = "input_output_alias={"
    start = hlo_text.find(key)
    if start < 0:
        return set()
    depth = 1
    i = start + len(key)
    while i < len(hlo_text) and depth:
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        i += 1
    block = hlo_text[start + len(key):i - 1]
    return {int(p) for p in _ALIAS_ENTRY.findall(block)}


def _entry_params(hlo_text) -> List[Tuple[int, str]]:
    comps = parse_computations(hlo_text)
    entry = comps.get("__entry__")
    if entry is None:
        return []
    params = []
    for op in entry.ops:
        if op.opcode != "parameter":
            continue
        n = _PARAM_NUM.search(op.line)
        if n:
            params.append((int(n.group(1)), op.type_str))
    return params


def _entry_output_avals(hlo_text: str) -> List[str]:
    comps = parse_computations(hlo_text)
    entry = comps.get("__entry__")
    if entry is None:
        return []
    root = next((op for op in entry.ops if "ROOT" in op.line), None)
    if root is None and entry.ops:
        root = entry.ops[-1]
    if root is None:
        return []
    return [f"{dtype}[{dims}]"
            for dtype, dims in _SHAPE_RE.findall(root.type_str)]


def parse_declared_donors(lowered_text: str) -> Set[int]:
    """Arg numbers carrying a donation marker in lowered StableHLO."""
    return {int(n) for n in _STABLEHLO_DONOR.findall(lowered_text)}


def check_donation(hlo_text: str, *, subject: str, site: str,
                   min_bytes: int = MIN_DONATION_BYTES,
                   lowered_text: str = ""
                   ) -> List[CompiledDiagnostic]:
    donated = parse_io_aliases(hlo_text)
    if lowered_text:
        donated |= parse_declared_donors(lowered_text)
    params = _entry_params(hlo_text)
    outputs: Dict[str, int] = {}
    for aval in _entry_output_avals(hlo_text):
        outputs[aval] = outputs.get(aval, 0) + 1
    # outputs already claimed by donated params can't indict anyone else
    for num, type_str in params:
        if num not in donated:
            continue
        for aval in [f"{d}[{dims}]" for d, dims in _SHAPE_RE.findall(type_str)]:
            if outputs.get(aval, 0) > 0:
                outputs[aval] -= 1

    offenders = []
    wasted = 0
    for num, type_str in params:
        if num in donated:
            continue
        avals = [f"{d}[{dims}]" for d, dims in _SHAPE_RE.findall(type_str)]
        if len(avals) != 1:
            continue
        aval = avals[0]
        nbytes = _type_bytes(type_str)
        if nbytes < min_bytes or outputs.get(aval, 0) <= 0:
            continue
        outputs[aval] -= 1
        offenders.append({"parameter": num, "aval": aval, "bytes": nbytes})
        wasted += nbytes
    if not offenders:
        return []
    return [diag(
        NON_DONATED_BUFFER, SEV_ERROR, subject, site,
        f"{len(offenders)} carried buffer(s) not donated "
        f"({wasted / 2**20:.2f} MiB held twice at peak): parameters "
        + ", ".join(f"#{o['parameter']} {o['aval']}" for o in offenders[:4])
        + " have same-shaped outputs but no input_output_alias — pass "
          "donate_argnums at the jit site",
        offenders=offenders, wasted_bytes=wasted)]
