"""Serving driver: an optimized pipeline under live traffic.

Routes real decoding traffic through the online serving stack:
``PipelineServer`` admission/micro-batching on top of ``JaxBackend``,
whose generation chunks ride the persistent continuous batcher
(``serving/scheduler.py``) — so concurrent requests coalesce twice:
merged ``Backend.submit`` chunks at the dispatch layer, shared decode
slots at the model layer.

The served plan is a *registry-validated* pipeline (the workload's
initial plan with every LLM op pointed at ``--arch``), not a hardcoded
request mix: swap in any ``SearchResult.best().pipeline`` the optimizer
produced.

``--tenants`` switches to the multi-tenant host: a comma-separated
``name=workload[:weight]`` roster (e.g.
``legal=cuad:2,medical=medec``) served by one ``MultiPipelineServer``
over one shared ``JaxBackend`` — different tenants' requests coalesce
into the same submit chunks and decode slots, admission is
weighted-fair across the roster.

``--policy adaptive --slo-s N`` swaps in the control plane's feedback
policy (SLO-sensing micro-batch window + per-tenant shedding; SLO
targets are seconds everywhere — ``--slo-ms`` survives as a deprecated
alias); ``--swap-after N`` demonstrates the drain-free hot plan swap
under live traffic and prints the swap record; ``--reopt`` attaches a
``ReoptLoop`` that reservoir-samples the served documents and runs one
re-optimization pass against the live backend once the trace drains,
promoting (``auto``) or proposing (``propose``) a Pareto-better plan.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --requests 8 --slots 4 --rps 0
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --tenants legal=cuad:2,medical=medec --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --policy adaptive --slo-s 2 --swap-after 4 --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --requests 8 --reopt --reopt-mode propose
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --full-width --requests 8    # published config (on a TPU)

``--full-width`` serves the architecture's published config instead of
its reduced smoke config. The command exits non-zero when any request
failed. ``main()`` keeps JAX's persistent compile cache where
``JAX_COMPILATION_CACHE_DIR`` says, or else in ``.jax_cache/`` at the
root of the checkout.
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import time
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.engine.workloads import WORKLOADS
from repro.pipeline.model import as_config
from repro.serving.control import AdaptivePolicy, ControlPolicy
from repro.serving.multi_server import MultiPipelineServer, TenantSpec
from repro.serving.pipeline_server import PipelineServer, ServeTicket
from repro.serving.reopt import ReoptLoop


#: the fixed compile-cache directory used when JAX_COMPILATION_CACHE_DIR
#: is unset: the root of the checkout, so every run from it hits the same
#: cache (a temporary or per-process path would never hit)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry point (never
    at import). With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it
    itself and nothing is set here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def failed_tickets(tickets: List[ServeTicket]) -> List[ServeTicket]:
    """Tickets that resolved with an error instead of output docs."""
    return [tk for tk in tickets if tk.error is not None]


def pipeline_for(workload, arch: str) -> Dict[str, Any]:
    """The workload's initial plan with every LLM operator pointed at
    ``arch`` — validated against the operator registry by the server."""
    config = as_config(workload.initial_pipeline)
    ops = [dict(op, model=arch) if "model" in op else dict(op)
           for op in config["operators"]]
    return {"name": f"{config['name']}@{arch}", "operators": ops}


def _policy_for(name: str, *, max_queue: int
                ) -> Optional[ControlPolicy]:
    """CLI policy selector: None keeps the server's default
    (StaticPolicy); "adaptive" senses recent SLO attainment and sheds
    per tenant (the host then needs ``--slo-ms``)."""
    if name == "static":
        return None
    if name == "adaptive":
        return AdaptivePolicy(max_queue=max_queue)
    raise SystemExit(f"--policy must be static or adaptive, got {name!r}")


def _resolve_slo(slo_s: Optional[float], slo_ms: Optional[float],
                 ) -> Optional[float]:
    """One SLO unit: seconds. ``slo_ms`` is the deprecated
    milliseconds alias; an explicit ``slo_s`` wins when both are
    passed."""
    if slo_ms is not None:
        warnings.warn("slo_ms is deprecated; pass slo_s (seconds)",
                      DeprecationWarning, stacklevel=3)
        if slo_s is None:
            slo_s = slo_ms / 1000.0
    return slo_s


def _swap_variant(plan: Dict[str, Any]) -> Dict[str, Any]:
    """A same-shape stand-in for an optimizer's next plan: the swap
    demo needs a second analyzable pipeline that hashes differently."""
    ops = [dict(op) for op in plan["operators"]]
    ops[0] = dict(ops[0], prompt=ops[0]["prompt"] + " Be concise.")
    return {"name": plan["name"] + "_v2", "operators": ops}


def _print_swap(record: Dict[str, Any]) -> None:
    before = record["before"]
    print(f"[swap] {record['old_plan']} ({record['old_hash'][:8]}) -> "
          f"{record['new_plan']} ({record['new_hash'][:8]}) at "
          f"t={record['at']:.2f}s; recent before swap: n={before['n']} "
          f"p95 {before['p95_latency_s']:.2f}s")


def _print_reopt(entry: Dict[str, Any]) -> None:
    where = f" tenant {entry['tenant']}" if entry.get("tenant") else ""
    head = (f"[reopt]{where} {entry['status']} "
            f"({entry['sampled']}/{entry['seen']} docs sampled)")
    if entry["status"] in ("promoted", "proposed"):
        inc, cand = entry["incumbent"], entry["candidate"]
        print(f"{head}: {inc['plan']} (acc {inc['acc']:.2f}, "
              f"cost {inc['cost']:.4f}) -> {cand['note']} "
              f"(acc {cand['acc']:.2f}, cost {cand['cost']:.4f})")
    else:
        print(f"{head}: {entry.get('reason', 'no dominating candidate')}")


def _reopt_loop(server, workload, *, mode: str, budget: int,
                seed: int) -> ReoptLoop:
    """The CLI's serve-and-optimize attachment: sample every served
    document (small trace), search against the live backend."""
    return ReoptLoop(server, workload, mode=mode, budget=budget,
                     seed=seed, reservoir_size=8, min_samples=2)


def _drive(server, submits, *, rps: float, seed: int,
           after_drain: Optional[Callable[[], None]] = None,
           close_backend: bool = True
           ) -> Tuple[List[ServeTicket], Dict[str, Any]]:
    """Shared open-loop drive: start the server, pace the ``submits``
    callables (each admits one request) at Poisson ``rps`` (0 = all at
    once), drain, run ``after_drain`` (the re-optimization hook — the
    backend is still open), shut down (closing the backend unless
    ``close_backend=False``), and report against wall time."""
    rng = random.Random(seed)
    t0 = time.monotonic()
    server.start()
    try:
        tickets = []
        for submit in submits:
            if rps > 0:
                time.sleep(rng.expovariate(rps))
            tickets.append(submit())
        server.drain()
        if after_drain is not None:
            after_drain()
    finally:
        server.shutdown(close_backend=close_backend)
    return tickets, server.report(elapsed_s=time.monotonic() - t0)


def serve_demo(arch: str, *, requests: int = 8, slots: int = 4,
               max_new: int = 8, rps: float = 0.0, workload: str = "medec",
               max_batch: Optional[int] = None, workers: int = 2,
               seed: int = 0, verbose: bool = True,
               policy: str = "static", slo_s: Optional[float] = None,
               max_queue: int = 16, swap_after: int = 0,
               reopt: bool = False, reopt_mode: str = "auto",
               reopt_budget: int = 8, slo_ms: Optional[float] = None,
               reduced: bool = True, backend: Optional[Any] = None
               ) -> Tuple[List[ServeTicket], Dict[str, Any]]:
    """End-to-end online serving demo on real JAX decoding.

    Submits ``requests`` documents against the workload's pipeline —
    open-loop Poisson pacing at ``rps`` requests/s (``rps=0``: all at
    once) — drains, and returns ``(tickets, stats report)``. ``--slots``
    sizes the continuous batcher's decode batch; ``max_batch`` (default
    ``2 * slots``) sizes the server's coalescing window so one merged
    chunk keeps the decode slots saturated with overflow queued.

    ``policy="adaptive"`` runs the control plane's feedback policy
    (requires ``slo_s``, in seconds; ``slo_ms`` is a deprecated
    milliseconds alias). ``swap_after=N`` hot-swaps the served plan
    to a prompt variant after the Nth submission — in-flight requests
    finish on the old plan, later ones ride the new one — and prints
    the swap record the report also carries. ``reopt=True`` attaches a
    :class:`~repro.serving.reopt.ReoptLoop` that samples the served
    documents and runs one re-optimization pass once the trace drains
    (the live backend is still open), auto-promoting or proposing per
    ``reopt_mode``.

    ``reduced=False`` serves the published config at full width. A
    caller that passes its own ``backend`` keeps it open after the
    drain, e.g. to read the served params; otherwise the demo builds one
    and closes it. A passed backend must have been built with this
    call's ``seed``, ``max_new``, ``slots`` and ``reduced``, or the demo
    raises ``ValueError`` rather than serve another configuration than
    it reports.
    """
    from repro.engine.backend import JaxBackend  # jax import is heavy

    slo_s = _resolve_slo(slo_s, slo_ms)
    w = WORKLOADS[workload]()
    plan = pipeline_for(w, arch)
    own_backend = backend is None
    if own_backend:
        backend = JaxBackend(seed=seed, max_new_tokens=max_new,
                             decode_slots=slots, reduced=reduced)
    else:
        want = {"seed": seed, "max_new_tokens": max_new,
                "DECODE_SLOTS": slots, "reduced": reduced}
        got = {k: getattr(backend, k, None) for k in want}
        if got != want:
            raise ValueError(f"serve_demo: the backend was built with "
                             f"{got}, not this demo's {want}")
    max_batch = max_batch or max(1, 2 * slots)
    server = PipelineServer(plan, backend, max_inflight=4 * max_batch,
                            max_batch=max_batch, batch_window_s=0.01,
                            workers=workers, seed=seed, slo_s=slo_s,
                            policy=_policy_for(policy,
                                               max_queue=max_queue))
    loop = (_reopt_loop(server, w, mode=reopt_mode, budget=reopt_budget,
                        seed=seed) if reopt else None)
    docs = [dict(w.sample[i % len(w.sample)], id=f"r{i}")
            for i in range(requests)]

    def submit(i: int, doc: Dict[str, Any]) -> ServeTicket:
        if swap_after and i == swap_after:
            _print_swap(server.swap_plan(_swap_variant(plan)))
        return server.submit(doc)

    def reoptimize() -> None:
        assert loop is not None
        _print_reopt(loop.run_once())

    tickets, report = _drive(
        server, [lambda i=i, d=doc: submit(i, d)
                 for i, doc in enumerate(docs)],
        rps=rps, seed=seed,
        after_drain=reoptimize if loop is not None else None,
        close_backend=own_backend)
    if verbose:
        for tk in tickets:
            if tk.error is not None:
                print(f"  req {tk.rid}: FAILED "
                      f"{type(tk.error).__name__}: {tk.error}")
                continue
            n_out = len(tk.docs) if tk.docs is not None else 0
            st = tk.stats
            print(f"  req {tk.rid}: {n_out} output docs in "
                  f"{tk.latency_s:.2f}s (queue {tk.queue_wait_s:.2f}s) "
                  f"{st.in_tokens if st else 0} in-toks "
                  f"{st.out_tokens if st else 0} out-toks")
        lat = report["latency_s"]
        print(f"[serve] {report['completed']}/{report['requests']} requests "
              f"in {report['elapsed_s']:.1f}s "
              f"({report['throughput_rps']:.2f} req/s) | "
              f"latency p50 {lat['p50']:.2f}s p95 {lat['p95']:.2f}s | "
              f"{report['batches']} batches "
              f"(mean size {report['mean_batch_size']:.1f}) | "
              f"{report['dispatch']['submit_calls']} submit calls")
        print(f"[serve] control: {report['control']} | "
              f"swaps: {len(report['swaps'])}")
    return tickets, report


def parse_tenants(spec: str, arch: str
                  ) -> List[Tuple[TenantSpec, str]]:
    """Parse a ``name=workload[:weight]`` roster into
    ``(TenantSpec, workload_key)`` pairs, each tenant serving its
    workload's pipeline pointed at ``arch``."""
    out: List[Tuple[TenantSpec, str]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, rest = part.partition("=")
        if not rest:
            raise SystemExit(f"--tenants entry {part!r}: expected "
                             f"name=workload[:weight]")
        workload, _, weight = rest.partition(":")
        if not name.strip():
            raise SystemExit(f"--tenants entry {part!r}: empty tenant "
                             f"name (expected name=workload[:weight])")
        if workload not in WORKLOADS:
            raise SystemExit(f"--tenants entry {part!r}: unknown workload "
                             f"{workload!r} (have {sorted(WORKLOADS)})")
        try:
            w = float(weight) if weight else 1.0
        except ValueError:
            raise SystemExit(f"--tenants entry {part!r}: weight "
                             f"{weight!r} is not a number") from None
        out.append((TenantSpec(
            name=name.strip(), weight=w,
            pipeline=pipeline_for(WORKLOADS[workload](), arch)), workload))
    if not out:
        raise SystemExit("--tenants: empty roster")
    return out


def serve_multi_demo(arch: str, tenants: str, *, requests: int = 8,
                     slots: int = 4, max_new: int = 8, rps: float = 0.0,
                     max_batch: Optional[int] = None, workers: int = 2,
                     seed: int = 0, verbose: bool = True,
                     policy: str = "static",
                     slo_s: Optional[float] = None, max_queue: int = 16,
                     swap_after: int = 0, reopt: bool = False,
                     reopt_mode: str = "auto", reopt_budget: int = 8,
                     slo_ms: Optional[float] = None, reduced: bool = True
                     ) -> Tuple[List[ServeTicket], Dict[str, Any]]:
    """Multi-tenant online serving on real JAX decoding: the roster's
    plans share one backend; requests round-robin across tenants at the
    submission side and coalesce across tenants inside the host.
    ``swap_after=N`` hot-swaps the *first* tenant's plan after the Nth
    submission; ``reopt=True`` re-optimizes every tenant from its own
    reservoir once the trace drains; ``reduced=False`` serves the
    published config."""
    from repro.engine.backend import JaxBackend  # jax import is heavy

    slo_s = _resolve_slo(slo_s, slo_ms)
    roster = parse_tenants(tenants, arch)
    specs = [spec for spec, _ in roster]
    workloads = {spec.name: WORKLOADS[wname]() for spec, wname in roster}
    # tenant name keys the roster; its workload's sample feeds traffic
    samples = {name: w.sample for name, w in workloads.items()}
    backend = JaxBackend(seed=seed, max_new_tokens=max_new,
                         decode_slots=slots, reduced=reduced)
    max_batch = max_batch or max(1, 2 * slots)
    server = MultiPipelineServer(specs, backend,
                                 max_inflight=4 * max_batch,
                                 max_batch=max_batch,
                                 batch_window_s=0.01, workers=workers,
                                 seed=seed, slo_s=slo_s,
                                 policy=_policy_for(policy,
                                                    max_queue=max_queue))
    loop = (_reopt_loop(server, workloads, mode=reopt_mode,
                        budget=reopt_budget, seed=seed)
            if reopt else None)

    def submit(i: int, tenant: str, doc: Dict[str, Any]) -> ServeTicket:
        if swap_after and i == swap_after:
            _print_swap(server.swap_plan(
                _swap_variant(specs[0].pipeline), tenant=specs[0].name))
        return server.submit(tenant, doc)

    def reoptimize() -> None:
        assert loop is not None
        for entry in loop.run_all():
            _print_reopt(entry)

    submits = []
    for i in range(requests):
        spec = specs[i % len(specs)]
        sample = samples[spec.name]
        doc = dict(sample[i % len(sample)], id=f"{spec.name}-r{i}")
        submits.append(lambda i=i, t=spec.name, d=doc: submit(i, t, d))
    tickets, report = _drive(server, submits, rps=rps, seed=seed,
                             after_drain=reoptimize if loop is not None
                             else None)
    if verbose:
        print(f"[serve] {report['completed']}/{report['requests']} "
              f"requests in {report['elapsed_s']:.1f}s | "
              f"{report['batches']} batches "
              f"(mean size {report['mean_batch_size']:.1f}) | "
              f"{report['dispatch']['submit_calls']} submit calls")
        for name, rep in report["tenants"].items():
            print(f"  tenant {name:12s} (w={rep['weight']}): "
                  f"{rep['completed']} served, "
                  f"{rep['dispatched']['requests']} dispatched reqs, "
                  f"p50 {rep['latency_s']['p50']:.2f}s")
        for tk in failed_tickets(tickets):
            print(f"  req {tk.rid} ({tk.tenant}): FAILED "
                  f"{type(tk.error).__name__}: {tk.error}")
    return tickets, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot width of the continuous batcher")
    ap.add_argument("--rps", type=float, default=0.0,
                    help="open-loop Poisson arrival rate (0: all at once)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--workload", default="medec",
                    choices=sorted(WORKLOADS))
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", default=None,
                    help="multi-tenant roster: name=workload[:weight],"
                         "... — serve all tenants from one host "
                         "(e.g. legal=cuad:2,medical=medec)")
    ap.add_argument("--policy", default="static",
                    choices=["static", "adaptive"],
                    help="control policy: static (fixed window, global "
                         "backpressure) or adaptive (SLO-sensing window "
                         "+ per-tenant shedding; requires --slo-s)")
    ap.add_argument("--slo-s", type=float, default=None,
                    help="per-request latency SLO in seconds the "
                         "adaptive policy senses against")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="deprecated alias of --slo-s (milliseconds)")
    ap.add_argument("--max-queue", type=int, default=16,
                    help="adaptive policy's per-tenant admitted-queue "
                         "bound")
    ap.add_argument("--swap-after", type=int, default=0,
                    help="hot-swap the served plan (first tenant with "
                         "--tenants) to a prompt variant after N "
                         "submissions; prints the swap record")
    ap.add_argument("--reopt", action="store_true",
                    help="attach a ReoptLoop: reservoir-sample served "
                         "documents and run one background "
                         "re-optimization pass after the trace drains")
    ap.add_argument("--reopt-mode", default="auto",
                    choices=["auto", "propose"],
                    help="auto-promote a dominating candidate through "
                         "swap_plan, or emit a PromotionProposal")
    ap.add_argument("--reopt-budget", type=int, default=8,
                    help="evaluation budget of the background search")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the architecture's published config "
                         "instead of its reduced smoke config")
    args = ap.parse_args()
    # keep libtpu's logs out of its fixed default directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_compile_cache()
    if args.tenants:
        tickets, _ = serve_multi_demo(
            args.arch, args.tenants, requests=args.requests,
            slots=args.slots, rps=args.rps, max_new=args.max_new,
            max_batch=args.max_batch, workers=args.workers, seed=args.seed,
            policy=args.policy, slo_s=args.slo_s, slo_ms=args.slo_ms,
            max_queue=args.max_queue, swap_after=args.swap_after,
            reopt=args.reopt, reopt_mode=args.reopt_mode,
            reopt_budget=args.reopt_budget, reduced=not args.full_width)
    else:
        tickets, _ = serve_demo(
            args.arch, requests=args.requests, slots=args.slots,
            rps=args.rps, max_new=args.max_new, workload=args.workload,
            max_batch=args.max_batch, workers=args.workers,
            seed=args.seed, policy=args.policy, slo_s=args.slo_s,
            slo_ms=args.slo_ms, max_queue=args.max_queue,
            swap_after=args.swap_after, reopt=args.reopt,
            reopt_mode=args.reopt_mode, reopt_budget=args.reopt_budget,
            reduced=not args.full_width)
    failed = failed_tickets(tickets)
    if failed:
        print(f"[serve] {len(failed)}/{len(tickets)} requests failed",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
