"""Compile-path static analyzer: jaxpr / HLO / Pallas lint.

PR 6 proved the shape one layer up (field-flow lint as a zero-token
reject gate over pipeline rewrites); this package applies it to the
compiled tier: typed diagnostics over traced jaxprs, optimized HLO, and
Pallas kernel resource envelopes, wired into ``python -m
repro.launch.lint --compile``, the ``JaxBackend`` construction gate, and
the CI ``compile-lint`` job. See ``diagnostics`` for the code table.
"""

from repro.analysis.compiled.audit import (audit_kernels,  # noqa: F401
                                           audit_model)
from repro.analysis.compiled.diagnostics import (  # noqa: F401
    ALL_CODES, DTYPE_UPCAST, HOST_TRANSFER, LOOP_TRANSFER,
    NON_DONATED_BUFFER, PALLAS_BLOCK_SHAPE, PALLAS_VMEM, RECOMPILE_RISK,
    SEV_ERROR, SEV_WARNING, CompiledAnalysisError,
    CompiledDiagnostic, CompiledReport, merge_reports)
from repro.analysis.compiled.hlo_lint import (check_donation,  # noqa: F401
                                              check_transfers,
                                              parse_declared_donors,
                                              parse_io_aliases)
from repro.analysis.compiled.jaxpr_lint import (  # noqa: F401
    check_dtype_upcast, f32_dot_share)
from repro.analysis.compiled.pallas_lint import (  # noqa: F401
    audit_kernel, check_tpu_tiling, default_kernel_cases)
from repro.analysis.compiled.recompile import (  # noqa: F401
    check_serving_recompile, prefill_shape_census)
