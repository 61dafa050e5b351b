"""Pallas resource lint: block divisibility + VMEM budget per kernel.

For each of the four in-tree kernels the audit replays the *ops.py
wrapper's* padding arithmetic (head_dim to 128 lanes, sequence/capacity
axes to block multiples) and then checks the contract the raw kernel
actually requires:

- every blocked axis must divide evenly after padding (a violation means
  the grid silently drops the ragged tail — exactly the ``ssd_scan``
  ``s % chunk`` truncation bug this lint exists to catch);
- every block obeys the TPU tiling rule (:func:`check_tpu_tiling`): its
  last two dims are multiples of (8, 128) or equal the array's. The
  compiler refuses other blocks, while interpret mode runs them. The
  ``ssd_scan`` audit does not apply it yet: that kernel's per-head scalar
  and ``dt`` blocks break the rule and it has never compiled for a chip;
- the per-grid-step VMEM working set — input + output block tiles
  double-buffered (Pallas pipelines the next tile's DMA against compute)
  plus f32 scratch — must fit the per-core VMEM of ``repro.hardware.HW``.

``default_kernel_cases()`` yields the shapes the repo actually launches:
the reduced-config model dims crossed with both the kernel-bench block
sizes and the kernels' production defaults. The strict CLI gate runs
these; seeded-defect tests call the audit functions with hostile shapes.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from repro.analysis.compiled.diagnostics import (
    PALLAS_BLOCK_SHAPE, PALLAS_VMEM, SEV_ERROR, CompiledDiagnostic, diag)
from repro.hardware import HW

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4,
                "int8": 1, "int32": 4}

Tile = Tuple[Tuple[int, ...], str]


def _tile_bytes(tiles: Iterable[Tile]) -> int:
    total = 0
    for shape, dtype in tiles:
        n = 1
        for d in shape:
            n *= d
        total += n * _DTYPE_BYTES[dtype]
    return total


def _check_divisible(subject: str, kernel: str, axis: str, size: int,
                     block: int) -> List[CompiledDiagnostic]:
    if block <= 0:
        return [diag(PALLAS_BLOCK_SHAPE, SEV_ERROR, subject, kernel,
                     f"kernel {kernel!r}: block for axis {axis!r} must be "
                     f"positive, got {block}", axis=axis, block=block)]
    if size % block != 0:
        return [diag(
            PALLAS_BLOCK_SHAPE, SEV_ERROR, subject, kernel,
            f"kernel {kernel!r}: axis {axis!r} of size {size} is not "
            f"divisible by block {block} — the grid drops the ragged "
            f"tail ({size % block} elements) silently",
            axis=axis, size=size, block=block)]
    return []


def check_tpu_tiling(subject: str, kernel: str, operand: str,
                     block: Tuple[int, ...], array: Tuple[int, ...]
                     ) -> List[CompiledDiagnostic]:
    """The TPU compiler's block rule: each of a block's last two dims
    (the last one only, for a 1-D block) is a multiple of 8 and 128
    respectively, or equals the array's dim there."""
    n = min(2, len(block))
    out: List[CompiledDiagnostic] = []
    for blk, arr, mult in zip(block[-n:], array[-n:], (8, 128)[-n:]):
        if blk != arr and blk % mult != 0:
            out.append(diag(
                PALLAS_BLOCK_SHAPE, SEV_ERROR, subject, kernel,
                f"kernel {kernel!r}: {operand} block {tuple(block)} on "
                f"array {tuple(array)} breaks the TPU tiling rule — dim "
                f"{blk} is neither a multiple of {mult} nor the array's "
                f"{arr}, so the chip's compiler refuses it",
                axis=operand, size=arr, block=blk))
    return out


def _check_tiles(subject: str, kernel: str,
                 tiles: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]
                 ) -> List[CompiledDiagnostic]:
    out: List[CompiledDiagnostic] = []
    for operand, (block, array) in tiles.items():
        out += check_tpu_tiling(subject, kernel, operand, block, array)
    return out


def _check_vmem(subject: str, kernel: str, io_tiles: List[Tile],
                scratch_tiles: List[Tile],
                vmem_bytes: Optional[int] = None
                ) -> List[CompiledDiagnostic]:
    budget = vmem_bytes if vmem_bytes is not None else HW["vmem_bytes"]
    working = 2 * _tile_bytes(io_tiles) + _tile_bytes(scratch_tiles)
    if working <= budget:
        return []
    return [diag(
        PALLAS_VMEM, SEV_ERROR, subject, kernel,
        f"kernel {kernel!r}: per-step VMEM working set "
        f"{working / 2**20:.1f} MiB (double-buffered tiles + scratch) "
        f"exceeds the {budget / 2**20:.0f} MiB budget — shrink the block "
        f"shapes", working_set_bytes=working, budget_bytes=budget)]


# -- per-kernel audits (mirror the ops.py wrappers' padding) ---------------


def audit_flash_attention(subject: str, *, b: int, s: int, h: int, kh: int,
                          hd: int, block_q: int = 512, block_k: int = 512,
                          dtype: str = "bfloat16",
                          vmem_bytes: Optional[int] = None
                          ) -> List[CompiledDiagnostic]:
    name = "flash_attention"
    out: List[CompiledDiagnostic] = []
    if kh <= 0 or h % kh != 0:
        out.append(diag(PALLAS_BLOCK_SHAPE, SEV_ERROR, subject, name,
                        f"kernel {name!r}: axis 'heads': {h} query heads "
                        f"not divisible by {kh} kv heads",
                        axis="heads", size=h, block=kh))
        return out
    hd_pad = max(128, -(-hd // 128) * 128)
    bq = min(block_q, max(s, 8))
    bk = min(block_k, max(s, 8))
    s_pad = max(-(-s // bq) * bq, -(-s // bk) * bk) if bq > 0 and bk > 0 else s
    out += _check_divisible(subject, name, "seq(q)", s_pad, bq)
    out += _check_divisible(subject, name, "seq(k)", s_pad, bk)
    out += _check_divisible(subject, name, "head_dim", hd_pad, 128)
    if any(d.code == PALLAS_BLOCK_SHAPE for d in out):
        return out
    g = h // kh
    q_tile = ((1, 1, 1, bq, hd_pad), (b, kh, g, s_pad, hd_pad))
    kv_tile = ((1, 1, bk, hd_pad), (b, kh, s_pad, hd_pad))
    tiles = {"q": q_tile, "k": kv_tile, "v": kv_tile, "out": q_tile}
    out += _check_tiles(subject, name, tiles)
    if out:
        return out
    io = [(blk, dtype) for blk, _ in tiles.values()]
    scratch = [((bq, 1), "float32"), ((bq, 1), "float32"),
               ((bq, hd_pad), "float32")]
    out += _check_vmem(subject, name, io, scratch, vmem_bytes)
    return out


def audit_flash_decode(subject: str, *, b: int, s: int, h: int, kh: int,
                       hd: int, block_s: int = 512, dtype: str = "bfloat16",
                       vmem_bytes: Optional[int] = None
                       ) -> List[CompiledDiagnostic]:
    name = "flash_decode"
    out: List[CompiledDiagnostic] = []
    if kh <= 0 or h % kh != 0:
        out.append(diag(PALLAS_BLOCK_SHAPE, SEV_ERROR, subject, name,
                        f"kernel {name!r}: axis 'heads': {h} query heads "
                        f"not divisible by {kh} kv heads",
                        axis="heads", size=h, block=kh))
        return out
    g = h // kh
    hd_pad = max(128, -(-hd // 128) * 128)
    bs = min(block_s, max(s, 8))
    s_pad = -(-s // bs) * bs if bs > 0 else s
    out += _check_divisible(subject, name, "seq", s_pad, bs)
    out += _check_divisible(subject, name, "head_dim", hd_pad, 128)
    if any(d.code == PALLAS_BLOCK_SHAPE for d in out):
        return out
    # the wrapper hands the kernel a (B, K, S, Hd) view of the cache;
    # valid_len rides in SMEM by scalar prefetch, outside the tiling rule
    q_tile = ((1, 1, g, hd_pad), (b, kh, g, hd_pad))
    kv_tile = ((1, 1, bs, hd_pad), (b, kh, s_pad, hd_pad))
    tiles = {"q": q_tile, "k": kv_tile, "v": kv_tile, "out": q_tile}
    out += _check_tiles(subject, name, tiles)
    if out:
        return out
    io = [(blk, dtype) for blk, _ in tiles.values()]
    scratch = [((g, 1), "float32"), ((g, 1), "float32"),
               ((g, hd_pad), "float32")]
    out += _check_vmem(subject, name, io, scratch, vmem_bytes)
    return out


def audit_moe_ffn(subject: str, *, g: int, e: int, c: int, d: int, f: int,
                  block_c: int = 128, block_f: int = 512,
                  dtype: str = "bfloat16",
                  vmem_bytes: Optional[int] = None
                  ) -> List[CompiledDiagnostic]:
    name = "moe_ffn"
    out: List[CompiledDiagnostic] = []
    bc = min(block_c, max(c, 8))
    bf = min(block_f, max(f, 128))
    c_pad = -(-c // bc) * bc if bc > 0 else c
    f_pad = -(-f // bf) * bf if bf > 0 else f
    out += _check_divisible(subject, name, "capacity", c_pad, bc)
    out += _check_divisible(subject, name, "ffn", f_pad, bf)
    if any(d.code == PALLAS_BLOCK_SHAPE for d in out):
        return out
    x_tile = ((1, 1, bc, d), (g, e, c_pad, d))
    w_in_tile = ((1, d, bf), (e, d, f_pad))
    tiles = {"x": x_tile, "w_gate": w_in_tile, "w_up": w_in_tile,
             "w_down": ((1, bf, d), (e, f_pad, d)), "out": x_tile}
    out += _check_tiles(subject, name, tiles)
    if out:
        return out
    io = [(blk, dtype) for blk, _ in tiles.values()]
    scratch = [((bc, d), "float32")]
    out += _check_vmem(subject, name, io, scratch, vmem_bytes)
    return out


def audit_ssd_scan(subject: str, *, b: int, s: int, h: int, g: int, p: int,
                   n: int, chunk: int, dtype: str = "float32",
                   vmem_bytes: Optional[int] = None
                   ) -> List[CompiledDiagnostic]:
    name = "ssd_scan"
    out: List[CompiledDiagnostic] = []
    if g <= 0 or h % g != 0:
        out.append(diag(PALLAS_BLOCK_SHAPE, SEV_ERROR, subject, name,
                        f"kernel {name!r}: axis 'heads': {h} heads not "
                        f"divisible by {g} groups",
                        axis="heads", size=h, block=g))
        return out
    out += _check_divisible(subject, name, "seq", s, chunk)
    if any(d.code == PALLAS_BLOCK_SHAPE for d in out):
        return out
    io = [((1, 1, chunk, p), dtype),        # x tile
          ((1, 1, chunk), dtype),           # dt tile
          ((1, 1, chunk, n), dtype),        # B tile
          ((1, 1, chunk, n), dtype),        # C tile
          ((1, 1, p, n), "float32"),        # h0 tile
          ((1, 1, chunk, p), dtype),        # y tile
          ((1, 1, p, n), "float32")]        # hf tile
    scratch = [((p, n), "float32")]
    out += _check_vmem(subject, name, io, scratch, vmem_bytes)
    return out


_AUDITS = {
    "flash_attention": audit_flash_attention,
    "flash_decode": audit_flash_decode,
    "moe_ffn": audit_moe_ffn,
    "ssd_scan": audit_ssd_scan,
}


def audit_kernel(kernel: str, subject: str,
                 **params: Any) -> List[CompiledDiagnostic]:
    if kernel not in _AUDITS:
        raise KeyError(f"unknown kernel {kernel!r} "
                       f"(known: {sorted(_AUDITS)})")
    return _AUDITS[kernel](subject, **params)


def default_kernel_cases() -> List[Tuple[str, Dict[str, Any]]]:
    """The (kernel, params) cases the repo actually launches: reduced
    model dims x {kernel-bench blocks, production-default blocks}, plus
    llama3.2-1b's published widths at ``JaxBackend``'s serving shape
    (4 decode slots x 112 cache positions)."""
    from repro.configs import get_config
    cases: List[Tuple[str, Dict[str, Any]]] = []
    full = get_config("llama3.2-1b")
    widths = dict(b=4, s=112, h=full.num_heads, kh=full.num_kv_heads,
                  hd=full.resolved_head_dim)
    cases.append(("flash_attention", widths))
    cases.append(("flash_decode", widths))
    lc = get_config("llama3.2-1b", reduced=True)
    hd = lc.head_dim or lc.d_model // lc.num_heads
    for bq, bk in ((64, 64), (512, 512)):
        cases.append(("flash_attention",
                      dict(b=2, s=64, h=lc.num_heads, kh=lc.num_kv_heads,
                           hd=hd, block_q=bq, block_k=bk)))
    for bs in (128, 512):
        cases.append(("flash_decode",
                      dict(b=2, s=512, h=lc.num_heads, kh=lc.num_kv_heads,
                           hd=hd, block_s=bs)))
    mc = get_config("granite-moe-1b-a400m", reduced=True)
    f = mc.moe_d_ff or mc.d_ff
    for bc, bf in ((16, 64), (128, 512)):
        cases.append(("moe_ffn",
                      dict(g=2, e=mc.num_experts, c=64, d=mc.d_model, f=f,
                           block_c=bc, block_f=bf)))
    sc = get_config("mamba2-370m", reduced=True)
    d_inner = sc.ssm_expand * sc.d_model
    heads = d_inner // sc.ssm_head_dim
    chunk = min(sc.ssm_chunk, 64)
    cases.append(("ssd_scan",
                  dict(b=2, s=64, h=heads, g=sc.ssm_groups,
                       p=sc.ssm_head_dim, n=sc.ssm_state, chunk=chunk)))
    return cases
