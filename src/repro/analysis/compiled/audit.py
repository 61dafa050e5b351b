"""Per-model / per-kernel audit orchestration for the compiled analyzer.

``audit_model`` runs every compile-path check against one architecture's
reduced config at the serving shapes ``JaxBackend`` actually uses:

- jaxpr tier (always): dtype-upcast lint over the decode-step and
  prefill traces, recompile-risk lint over the serving jit sites.
  Tracing + eval_shape only — milliseconds, safe for construction-time gates.
- HLO tier (``compile=True``): lowers and compiles the decode step with
  the scheduler's donation declaration, then runs the transfer and
  donation lints over the optimized module text. Seconds per model —
  the CLI/CI surface.

``audit_kernels`` sweeps the Pallas resource lint over
``default_kernel_cases()`` (or caller-supplied cases).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.compiled.diagnostics import CompiledReport
from repro.analysis.compiled.hlo_lint import check_donation, check_transfers
from repro.analysis.compiled.jaxpr_lint import check_dtype_upcast
from repro.analysis.compiled.pallas_lint import (audit_kernel,
                                                 default_kernel_cases)
from repro.analysis.compiled.recompile import check_serving_recompile

#: serving shapes mirrored from ``JaxBackend`` (MAX_PROMPT_TOKENS=96,
#: max_new_tokens=8, +8 slack) at a small slot count
AUDIT_SLOTS = 2
AUDIT_MAX_LEN = 112
AUDIT_MAX_PROMPT = 96


def _prefill_inputs(cfg) -> Dict[str, Any]:
    inputs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((AUDIT_SLOTS, 16), jnp.int32)}
    if cfg.family == "vlm":
        vd = cfg.vit_dim or cfg.d_model
        inputs["patch_embeds"] = jax.ShapeDtypeStruct(
            (AUDIT_SLOTS, cfg.num_patches, vd), jnp.float32)
    if cfg.is_encoder_decoder:
        inputs["frames"] = jax.ShapeDtypeStruct(
            (AUDIT_SLOTS, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    return inputs


def audit_model(arch: str, *, compile: bool = True,
                reduced: bool = True) -> CompiledReport:
    from repro.configs import get_config
    from repro.models import api
    from repro.serving.decode import serve_step_jit

    t0 = time.perf_counter()
    cfg = get_config(arch, reduced=reduced)
    report = CompiledReport(arch)

    params_shape = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg))
    cache_shape = jax.eval_shape(
        lambda: api.init_cache(cfg, AUDIT_SLOTS, AUDIT_MAX_LEN))
    token_shape = jax.ShapeDtypeStruct((AUDIT_SLOTS, 1), jnp.int32)

    # jaxpr tier ----------------------------------------------------------
    step_jit = serve_step_jit(cfg)
    step_fn = step_jit.__wrapped__
    report.extend(check_dtype_upcast(
        step_fn, params_shape, token_shape, cache_shape,
        subject=arch, site="decode_step", model_dtype=cfg.dtype))
    inputs = _prefill_inputs(cfg)
    report.extend(check_dtype_upcast(
        lambda p, **kw: api.prefill(p, cfg, AUDIT_MAX_LEN, **kw),
        params_shape, subject=arch, site="prefill",
        model_dtype=cfg.dtype, **inputs))
    report.extend(check_serving_recompile(
        cfg, subject=arch, max_prompt_tokens=AUDIT_MAX_PROMPT,
        max_len=AUDIT_MAX_LEN))

    # HLO tier ------------------------------------------------------------
    if compile:
        lowered = step_jit.lower(params_shape, token_shape, cache_shape)
        text = lowered.compile().as_text()
        report.extend(check_transfers(text, subject=arch,
                                      site="decode_step"))
        # CPU XLA drops donation from the optimized module, so hand the
        # lint the lowered StableHLO where the declaration survives
        report.extend(check_donation(text, subject=arch, site="decode_step",
                                     lowered_text=lowered.as_text()))

    report.analyze_s = time.perf_counter() - t0
    return report


def audit_kernels(cases: Optional[List[Tuple[str, Dict[str, Any]]]] = None
                  ) -> List[CompiledReport]:
    reports = []
    for kernel, params in (cases if cases is not None
                           else default_kernel_cases()):
        t0 = time.perf_counter()
        blocks = ",".join(f"{k}={v}" for k, v in params.items()
                          if k.startswith("block") or k == "chunk")
        label = f"{kernel}[{blocks}]"
        rep = CompiledReport(label)
        rep.extend(audit_kernel(kernel, label, **params))
        rep.analyze_s = time.perf_counter() - t0
        reports.append(rep)
    return reports
