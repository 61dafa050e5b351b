"""Chip smoke check: serve llama3.2-1b at its published width on one TPU.

Run from the root of a checkout, on a host with one TPU:

    python chip_smoke.py

Everything runs in this one process, which holds the chip. Phases:

1. serve: 8 ``medec`` requests go through the normal entry points,
   ``serve_demo`` -> ``PipelineServer`` -> ``Executor`` -> ``JaxBackend``
   -> ``ContinuousBatcher`` -> the jitted decode step. The model is
   llama3.2-1b at its published width (16 layers, d_model 2048, 32/8
   heads, d_ff 8192, vocab 128256) with random weights from seed 0, on 4
   decode slots with 8 new tokens per request. Fails if any ticket has an
   error, or any request produced fewer than 8 tokens or an id outside
   the vocabulary, or the params are not on the TPU.
2. logits: ``api.prefill`` with the served bf16 params on the TPU, for
   two of the served prompts, against ``api.forward`` with the same
   params cast to float32 on the host CPU at "highest" matmul precision.
   This checks the platform, not the model (there is no independent
   float32 model reference yet).
3. kernels: ``flash_attention`` and ``flash_decode`` compiled for the
   chip (the Pallas TPU kernel, never the interpreter) at llama3.2-1b
   widths, against their jnp references on the host CPU.

Exit 0 only when every phase passed; the last line of stdout is then
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
with the device as JAX reports it. Where JAX finds no TPU, or a phase
fails, it exits 1 and prints no such line. The wall times it prints are
of a smoke run, not a benchmark.

JAX's compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or
else to ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# JAX is imported inside the functions: main() settles JAX_PLATFORMS
# before the first import, and tests import this module on the CPU.

ARCH = "llama3.2-1b"
WORKLOAD = "medec"
REQUESTS, SLOTS, MAX_NEW, SEED = 8, 4, 8, 0
#: medec's one map operator writes each request's generated ids,
#: space-joined, to this output field (``JaxBackend._value_for``)
OUT_FIELD = "errors"
LOGIT_PROMPTS = 2

# Prefill logits, bf16 params and compute on the TPU against the same
# params in float32. bf16 keeps 8 significant bits (unit roundoff 2^-9);
# the hidden state is rounded at every matmul and residual add of 16
# layers, and those errors add in quadrature. At this init (embedding std
# 0.02, tied head) the logits' RMS is about 0.9 at d_model 2048. The same
# comparison at smaller widths and 4-16 layers on the CPU gave a relative
# L2 error of 1.1-1.3% and a max-abs error of 0.02-0.03 over 8192 logits.
# 128256 logits reach further into the tail, about 5 sigma of a 0.012
# error. The bounds leave about 3x that room. By estimate (no run checked
# it), fp8 compute, with a unit roundoff of 2^-4 (32x bf16's), would miss
# both.
LOGIT_RTOL = 0.04   # relative L2 error of the last-position logits
LOGIT_ATOL = 0.1    # max-abs error; argmax must agree past this margin

# Kernels read bf16 inputs, compute in float32 and write bf16; the
# reference computes in float32 from the same bf16 inputs. Rounding the
# output to bf16 costs half an ulp, 2^-9 relative to the binade, so each
# bound follows from the size of the kernel's outputs; the rest of it is
# for the float32 matmuls' passes on the MXU.
# - flash_attention: causal rows near the start copy single N(0, 1) v
#   rows, |x| < 8, where half an ulp is 0.0156 in [4, 8). The repo's
#   interpret-mode bf16 kernel tests use the same bound.
# - flash_decode: every output averages v over 97 positions, |x| < 2,
#   where half an ulp is 0.0039 in [1, 2). Its first chip run read
#   0.0019 (half an ulp in [0.5, 1)); the bound is about 3x that, which
#   an accumulator kept in bf16 would likely exceed (an estimate).
KERNEL_ATOL = {"flash_attention": 2e-2, "flash_decode": 6e-3}

KERNEL_B, KERNEL_S = SLOTS, 112   # JaxBackend's cache: 96 + 8 + 8 slack
DECODE_VALID_LEN = 97             # a ragged last block exercises the mask


def check_tickets(tickets: Sequence[Any], max_new: int, vocab: int
                  ) -> Tuple[int, List[str]]:
    """(tokens seen, problems) over the served tickets: every ticket
    resolved without error to one doc whose output field holds at least
    ``max_new`` ids, each in ``[0, vocab)``."""
    problems: List[str] = []
    n_tokens = 0
    for tk in tickets:
        if tk.error is not None:
            problems.append(f"request {tk.rid}: "
                            f"{type(tk.error).__name__}: {tk.error}")
            continue
        if not tk.docs or not tk.docs[0].get(OUT_FIELD):
            problems.append(f"request {tk.rid}: no {OUT_FIELD!r} output")
            continue
        toks = [int(t) for t in tk.docs[0][OUT_FIELD][0]["value"].split()]
        n_tokens += len(toks)
        if len(toks) < max_new:
            problems.append(f"request {tk.rid}: {len(toks)} tokens, "
                            f"expected {max_new}")
        bad = [t for t in toks if not 0 <= t < vocab]
        if bad:
            problems.append(f"request {tk.rid}: token ids {bad} outside "
                            f"[0, {vocab})")
    return n_tokens, problems


def compare_logits(got, ref, *, atol: float = LOGIT_ATOL,
                   rtol: float = LOGIT_RTOL
                   ) -> Tuple[Dict[str, float], List[str]]:
    """Error of ``got`` against the float32 ``ref`` (1-D logits): max-abs
    and relative L2 within bounds, and the same argmax wherever the
    reference's top-2 margin exceeds ``atol``."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    diff = got - ref
    top2 = np.sort(ref)[-2:]
    stats = {"max_abs": float(np.abs(diff).max()),
             "rel_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref)),
             "top2_margin": float(top2[1] - top2[0])}
    problems: List[str] = []
    if not np.isfinite(got).all():
        problems.append("non-finite logits")
    if not stats["max_abs"] <= atol:
        problems.append(f"max-abs error {stats['max_abs']} > {atol}")
    if not stats["rel_l2"] <= rtol:
        problems.append(f"relative L2 error {stats['rel_l2']} > {rtol}")
    if stats["top2_margin"] > atol and int(got.argmax()) != int(ref.argmax()):
        problems.append(f"argmax {int(got.argmax())} != reference "
                        f"{int(ref.argmax())} at margin "
                        f"{stats['top2_margin']}")
    return stats, problems


def serve_phase(backend) -> Tuple[List[Any], List[str]]:
    import jax

    from repro.launch.serve import serve_demo
    tickets, report = serve_demo(ARCH, requests=REQUESTS, slots=SLOTS,
                                 max_new=MAX_NEW, workload=WORKLOAD,
                                 seed=SEED, reduced=False, backend=backend)
    cfg, params = backend._model(ARCH)
    n_tokens, problems = check_tickets(tickets, MAX_NEW, cfg.vocab_size)
    where = {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}
    if where != {jax.devices()[0]}:
        problems.append(f"served params sit on {where}, not on "
                        f"{jax.devices()[0]}")
    print(f"[smoke] serve: {report['completed']}/{REQUESTS} requests, "
          f"{n_tokens} tokens, {report['failed']} failed")
    return tickets, problems


def logits_phase(backend, tickets: Sequence[Any]) -> List[str]:
    import jax
    import jax.numpy as jnp

    from repro.data.tokenizer import HashWordTokenizer
    from repro.engine.workloads import WORKLOADS
    from repro.launch.serve import pipeline_for
    from repro.models import api
    from repro.pipeline.protocols import OpRequest

    cfg, params = backend._model(ARCH)
    op = pipeline_for(WORKLOADS[WORKLOAD](), ARCH)["operators"][0]
    tok = HashWordTokenizer(cfg.vocab_size)
    prompts = [tok.encode(backend._prompt_for(OpRequest("map", op,
                                                        doc=tk.doc)))
               [:backend.MAX_PROMPT_TOKENS]
               for tk in tickets[:LOGIT_PROMPTS]]

    cpu = jax.devices("cpu")[0]
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")
    with jax.default_device(cpu):
        params32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                                jax.device_put(params, cpu))
    forward32 = jax.jit(lambda p, t: api.forward(p, cfg32, tokens=t)[0])

    problems: List[str] = []
    for i, ids in enumerate(prompts):
        tokens = jnp.asarray([ids], jnp.int32)
        logits, _ = api.prefill(params, cfg, len(ids), tokens=tokens)
        got = jax.device_get(logits[0, -1])
        with jax.default_device(cpu), \
                jax.default_matmul_precision("highest"):
            ref = jax.device_get(
                forward32(params32, jax.device_put(tokens, cpu))[0, -1])
        stats, found = compare_logits(got, ref)
        print(f"[smoke] logits prompt {i} ({len(ids)} tokens): "
              f"max-abs {stats['max_abs']} (tol {LOGIT_ATOL}), "
              f"rel-L2 {stats['rel_l2']} (tol {LOGIT_RTOL}), "
              f"top-2 margin {stats['top2_margin']}, "
              f"{'PASS' if not found else 'FAIL'}")
        problems += [f"prompt {i}: {p}" for p in found]
    return problems


def kernel_phase(cfg) -> List[str]:
    import jax
    import jax.numpy as jnp

    from repro.kernels import resolve_interpret
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.kernels.flash_decode.ref import decode_ref

    problems: List[str] = []
    if resolve_interpret(None):
        problems.append("kernels would run in interpret mode here")
    b, s = KERNEL_B, KERNEL_S
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)

    def rand(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    q = rand(keys[0], (b, s, h, hd))
    k = rand(keys[1], (b, s, kh, hd))
    v = rand(keys[2], (b, s, kh, hd))
    q1 = rand(keys[3], (b, 1, h, hd))
    n = jnp.asarray(DECODE_VALID_LEN, jnp.int32)
    cpu = jax.devices("cpu")[0]
    q_, k_, v_, q1_ = (x.astype(jnp.float32)
                       for x in jax.device_put((q, k, v, q1), cpu))
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        refs = {"flash_attention": attention_ref(q_, k_, v_, causal=True),
                "flash_decode": decode_ref(
                    q1_.reshape(b, kh, h // kh, hd), k_, v_,
                    DECODE_VALID_LEN).reshape(b, 1, h, hd)}
    cases = {"flash_attention": (lambda q, k, v: flash_attention(q, k, v),
                                 (q, k, v)),
             "flash_decode": (flash_decode, (q1, k, v, n))}
    for name, (fn, args) in cases.items():
        compiled = jax.jit(fn).lower(*args).compile()
        kernel_in_program = "tpu_custom_call" in compiled.as_text()
        out = np.asarray(compiled(*args), np.float32)
        err = float(np.abs(out - np.asarray(refs[name], np.float32)).max())
        tol = KERNEL_ATOL[name]
        ok = kernel_in_program and err <= tol
        print(f"[smoke] kernel {name} (B={b} S={s} H={h} K={kh} Hd={hd} "
              f"bf16): TPU kernel in program {kernel_in_program}, "
              f"max-abs {err} (tol {tol}), "
              f"{'PASS' if ok else 'FAIL'}")
        if not kernel_in_program:
            problems.append(f"{name}: no TPU kernel in the program")
        if not err <= tol:
            problems.append(f"{name}: max-abs error {err} > {tol}")
    return problems


def main() -> int:
    # keep libtpu's logs out of its fixed default directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the float32 reference runs on the host CPU beside the TPU
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    from repro.engine.backend import JaxBackend
    from repro.launch.serve import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX's first device is {dev}",
              file=sys.stderr)
        return 1
    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}")

    t0 = time.monotonic()
    failures: Dict[str, List[str]] = {}
    backend = JaxBackend(seed=SEED, max_new_tokens=MAX_NEW,
                         decode_slots=SLOTS,
                         reduced=False)
    try:
        tickets, failures["serve"] = serve_phase(backend)
        t1 = time.monotonic()
        failures["logits"] = logits_phase(backend, tickets)
        t2 = time.monotonic()
        cfg, _ = backend._model(ARCH)
        failures["kernels"] = kernel_phase(cfg)
        t3 = time.monotonic()
    finally:
        backend.close()
    for phase, found in failures.items():
        print(f"[smoke] phase {phase}: {'FAIL' if found else 'PASS'}")
    print(f"[smoke] wall time (smoke run, not a benchmark, compiles "
          f"included): serve {t1 - t0:.1f}s, logits {t2 - t1:.1f}s, "
          f"kernels {t3 - t2:.1f}s")
    problems = [f"{phase}: {p}" for phase, found in failures.items()
                for p in found]
    if problems:
        print("[smoke] FAILED:\n  " + "\n  ".join(problems), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
