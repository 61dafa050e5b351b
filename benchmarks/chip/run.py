"""Run one benchmark cell once, on the chip of the machine it starts on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout. The cell's configuration is served at its
published widths through the program's own path: ``PipelineServer`` ->
``Executor`` -> ``JaxBackend(reduced=False)`` -> ``ContinuousBatcher`` ->
the jitted decode step. Weights come from ``--seed`` (the backend draws
them), and so does the traffic (``traffic.py``).

A run: set-up (imports, the backend's weights and compile lint, the
compile cache, and a warm-up batch of the cell's own shapes), then a
measured window of ``--seconds``, then the check that decides
``correct`` (``oracle.py``) once the program's state is freed. With
``--trace 1`` the profiler records the window's start (a closed loop up
to the first completion after ``trace_seconds``, an open loop up to its
last arrival), and the per-layer metrics are reported; with
``--trace 0`` the end-to-end ones. The last line of stdout is the result
as one JSON object; the numbers compared for ``correct`` are also the
last lines of stderr. Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.

JAX's compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or
else to ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import layout, oracle, trace_reduce  # noqa: E402
from benchmarks.chip import traffic as T  # noqa: E402

#: how long after the window an open-loop request may still resolve
LATE_S = 60.0
now = time.perf_counter


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(jax, n: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's devices are {devs}")
    if len(devs) < n:
        raise NoAccelerator(f"the cell asks for {n} chips, JAX has "
                            f"{len(devs)}")
    return devs[:n]


def enable_compile_cache(jax, platform: str) -> None:
    """On a TPU, every program, eager operations included, goes to the
    persistent cache at a fixed path, so only a checkout's first run
    compiles. (The CPU tests keep no cache.)"""
    if platform != "tpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_config(keys: Dict[str, str], sizes: Dict[str, Any], cfg) -> None:
    """The served configuration is the file's, key by key."""
    wrong = {k: (sizes[k], getattr(cfg, attr)) for k, attr in keys.items()
             if sizes[k] != getattr(cfg, attr)}
    if wrong:
        raise ValueError(f"served config {cfg.name} differs from the "
                         f"configuration file: {wrong}")


class Probe:
    """The backend as the server sees it, plus the benchmark's eyes:
    a ``Backend.submit`` span in the trace, each submit's size and host
    time, and the tokens each request was served (read where the backend
    hands them to its output shaping)."""

    def __init__(self, inner, annotation):
        self._inner = inner
        self._annotation = annotation
        self.preferred_batch_size = inner.preferred_batch_size
        self.deterministic = getattr(inner, "deterministic", False)
        self.chunks: List[tuple] = []
        self.served: Dict[str, tuple] = {}
        self._ids: List[str] = []
        generate = inner._generate_batch

        def capture(model, texts):
            out = generate(model, texts)
            for doc_id, text, (toks, _) in zip(self._ids, texts, out):
                self.served[doc_id] = (text, [int(t) for t in toks])
            return out

        inner._generate_batch = capture

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def submit(self, requests):
        self._ids = [r.doc.get("id") for r in requests
                     if r.kind not in ("resolve", "equijoin")]
        with self._annotation(trace_reduce.BACKEND):
            t0 = now()
            out = self._inner.submit(requests)
            self.chunks.append((t0, now(), len(requests)))
        return out


@dataclass
class Req:
    doc: Dict[str, Any]
    due: float
    sent: float
    ticket: Any
    done_at: Optional[float] = None


class Recorder:
    """Submits documents and stamps each ticket's resolution on the
    benchmark's clock (a server request observer)."""

    def __init__(self, server, annotation):
        self.server = server
        self.annotation = annotation
        self.reqs: List[Req] = []
        self._by_rid: Dict[int, Req] = {}
        self._cond = threading.Condition()
        self.resolved = 0
        server.add_request_observer(self._observe)

    def _observe(self, tk, record) -> None:
        t = now()
        with self._cond:
            req = self._by_rid.get(tk.rid)
            if req is not None:
                req.done_at = t
            self.resolved += 1
            self._cond.notify_all()

    def submit(self, doc: Dict[str, Any], due: float) -> Req:
        sent = now()
        with self.annotation(trace_reduce.SUBMIT):
            with self._cond:
                tk = self.server.submit(doc)
                req = Req(doc, due, sent, tk)
                self._by_rid[tk.rid] = req
                self.reqs.append(req)
        return req

    def outstanding(self) -> int:
        return sum(1 for r in self.reqs if not r.ticket.done)

    def wait(self, seen: int, timeout: float) -> None:
        with self._cond:
            self._cond.wait_for(lambda: self.resolved > seen,
                                timeout=max(timeout, 0.0))


class TraceWindow:
    """The profiler over the window's start, under a ``bench.window`` span
    that tells the reduction where it lies. It stops at the first poll
    after ``seconds``; a closed loop polls when requests resolve, so its
    trace ends on a completion, and the ``Backend.submit`` span that just
    closed is in it (a span still open at the stop is not recorded).

    The host records the benchmark's own spans alone: the profiler's
    Python tracer and runtime spans slowed each decode tick about fivefold
    and stalled the process for some 30 s when the trace stopped (one TPU
    v5e, mamba2-370m)."""

    def __init__(self, jax, enabled: bool, seconds: float):
        self.jax, self.enabled, self.seconds = jax, enabled, seconds
        self.dir: Optional[str] = None
        self.active = False
        self.stop_at = float("inf")

    def start(self) -> None:
        if not self.enabled:
            return
        self.dir = tempfile.mkdtemp(prefix="chip-bench-trace-")
        options = self.jax.profiler.ProfileOptions()
        options.host_tracer_level = 1     # TraceAnnotation spans only
        options.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=options)
        self.span = self.jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.span.__enter__()
        self.active = True
        self.stop_at = now() + self.seconds

    def poll(self) -> None:
        if self.active and now() >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.active:
            self.span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.active = False

    def reduce(self) -> Optional[Dict[str, Any]]:
        if self.dir is None:
            return None
        try:
            return trace_reduce.reduce(trace_reduce.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Run:
    """What the metric readers read (``metrics/<name>.py``)."""
    cell: Dict[str, Any]
    traffic: Dict[str, Any]
    sizes: Dict[str, Any]
    family: Any
    peaks: Optional[Dict[str, Any]]
    seconds: float
    setup_s: float
    window: tuple
    reqs: List[Req]
    chunks: List[tuple]
    trace: Optional[Dict[str, Any]] = None

    def in_window(self) -> List[Req]:
        w0, w1 = self.window
        return [r for r in self.reqs if w0 <= r.due < w1]


def drive_closed(rec: Recorder, t, seed: int, seconds: float,
                 tw: TraceWindow) -> tuple:
    """Keep ``backlog`` documents outstanding for ``seconds``."""
    counts = T.word_counts(t, 1024, seed)
    backlog = [T.document(seed, i, counts[i]) for i in range(t["backlog"])]
    released = 0

    def release():
        nonlocal released
        doc = backlog[released] if released < len(backlog) else \
            T.document(seed, released, counts[released % len(counts)])
        released += 1
        rec.submit(doc, due=now())

    tw.start()
    w0 = now()
    end = w0 + seconds
    for _ in range(t["backlog"]):
        release()
    while now() < end:
        seen = rec.resolved
        rec.wait(seen, end - now())
        tw.poll()
        while rec.outstanding() < t["backlog"] and now() < end:
            release()
    return w0, end


def drive_open(rec: Recorder, t, seed: int, seconds: float,
               tw: TraceWindow) -> tuple:
    """Open loop: each document is submitted when it is due. The trace
    stops only once the last is sent: stopping it stalls the process for
    some seconds, which made arrivals up to 9 s late (one TPU v5e)."""
    due = T.arrivals(t, seconds, seed)
    docs = [T.document(seed, i, w)
            for i, w in enumerate(T.word_counts(t, len(due), seed))]
    tw.start()
    w0 = now()
    for offset, doc in zip(due, docs):
        target = w0 + offset
        delay = target - now()
        if delay > 0:
            time.sleep(delay)
        rec.submit(doc, due=target)
    end = w0 + seconds
    while now() < end:
        time.sleep(min(0.05, max(end - now(), 0.0)))
        tw.poll()
    return w0, end


def lateness(reqs: List[Req]) -> Dict[str, float]:
    late = [r.sent - r.due for r in reqs]
    return {"n": len(late), "p50_s": T.percentile(late, 50),
            "p99_s": T.percentile(late, 99), "max_s": max(late)}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, reduced: bool = False,
             sizes: Optional[Dict[str, Any]] = None,
             peaks: Optional[Dict[str, Any]] = None,
             control: bool = False, check: bool = True) -> Dict[str, Any]:
    """One run of cell ``name``. ``reduced``, ``sizes`` and ``peaks``
    serve the CPU tests (the program's smoke config, its sizes, stand-in
    peaks); ``control`` also reads the fp8 control and judges it, in the
    program's place, against the cell's limits (``out["control"]``); and
    ``check=False`` (a rate sweep) leaves the reference out, which makes
    the run not correct."""
    bench = layout.benchmark()
    cell = layout.cell(bench, name)
    cfg_file = layout.config(bench, cell["config"])
    t = layout.traffic(cell["traffic"])
    limits = layout.limits(name)
    fam = layout.family(cfg_file["family"])
    sizes = dict(cfg_file if sizes is None else sizes)

    import jax
    from jax import monitoring

    devs = chips(jax, cell["chips"], require_tpu)
    dev = devs[0]
    enable_compile_cache(jax, dev.platform)
    if peaks is None:
        peaks = layout.peaks(dev.device_kind)

    from repro.configs import get_config
    from repro.engine.backend import JaxBackend
    from repro.serving.pipeline_server import PipelineServer

    check_config(fam.PROGRAM_KEYS, sizes,
                 get_config(cfg_file["name"], reduced=reduced))
    srv = t["server"]
    backend = JaxBackend(seed=seed, max_new_tokens=srv["max_new_tokens"],
                         decode_slots=srv["decode_slots"], reduced=reduced)
    annotation = jax.profiler.TraceAnnotation
    probe = Probe(backend, annotation)
    op = dict(t["operator"], model=cfg_file["name"])
    server = PipelineServer(
        T.pipeline(t, cfg_file["name"]), probe,
        max_inflight=srv["max_inflight"], max_batch=srv["max_batch"],
        batch_window_s=srv["batch_window_s"], workers=srv["workers"],
        seed=seed)
    rec = Recorder(server, annotation)
    server.start()

    # warm-up: the cell's own shapes, on documents outside the traffic
    warm = [dict(T.document(seed, -1 - i, t["words_min"]), id=f"w{i}")
            for i in range(t["warmup_requests"])]
    for doc in warm:
        rec.submit(doc, due=now())
    for r in rec.reqs:
        if r.ticket.result(timeout=900) is None:
            raise RuntimeError("warm-up request returned nothing")
    rec.reqs.clear()
    setup_s = now() - T_START

    # JAX reports a program fetched from the persistent cache as a backend
    # compile too, and as a cache hit besides: compiled = compiles - hits
    compiles = {"backend_compile": 0, "cache_hit": 0}
    in_window = [True]

    def on_duration(event: str, duration: float, **_kw) -> None:
        if in_window[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles["backend_compile"] += 1

    def on_event(event: str, **_kw) -> None:
        if in_window[0] and event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hit"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    tw = TraceWindow(jax, trace, min(seconds, t["trace_seconds"]))
    drive = drive_closed if t["arrival"] == "closed" else drive_open
    w0, w1 = drive(rec, t, seed, seconds, tw)

    # close: an open loop waits for every due request; a closed backlog
    # drops what never started and finishes the batch in flight
    if t["arrival"] == "closed":
        server.shutdown(drain=False, timeout=LATE_S)
    else:
        deadline = now() + LATE_S
        for r in rec.reqs:
            r.ticket.wait(max(deadline - now(), 0.0))
    tw.stop()
    in_window[0] = False
    server.shutdown(drain=False, timeout=LATE_S)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    run = Run(cell=cell, traffic=t, sizes=sizes, family=fam, peaks=peaks,
              seconds=seconds, setup_s=setup_s, window=(w0, w1),
              reqs=list(rec.reqs), chunks=list(probe.chunks))
    run.trace = tw.reduce()
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in layout.cell_metrics(bench, name, kind):
        value = layout.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # which requests count: open loop, all that were due; closed, all the
    # server started (a queued document dropped at the close never was)
    from repro.serving.pipeline_server import ServerClosed
    attempted = [r for r in rec.reqs if t["arrival"] != "closed"
                 or not isinstance(r.ticket.error, ServerClosed)]
    failed = [r for r in attempted
              if not r.ticket.done or r.ticket.error is not None]
    served = {r.doc["id"]: r for r in attempted if r.ticket.done
              and r.ticket.error is None and r.doc["id"] in probe.served}
    mismatches: List[str] = []
    prompt_mismatches = 0
    for doc_id, r in served.items():
        text, toks = probe.served[doc_id]
        prompt_mismatches += text != T.prompt_text(op, r.doc)
        if len(toks) != srv["max_new_tokens"] or \
                not all(0 <= x < sizes["vocab_size"] for x in toks):
            mismatches.append(f"{doc_id}: {len(toks)} tokens, ids "
                              f"{min(toks)}..{max(toks)}")
            continue
        mismatches += oracle.output_mismatches(op, r.doc, r.ticket.docs,
                                               toks)
    missing = len(attempted) - len(failed) - len(served)
    tokens = {doc_id: probe.served[doc_id][1] for doc_id in served}

    # free the program's state before the reference takes the chip
    probe.close()
    del server, probe, backend, rec
    gc.collect()

    bad = {m.split(":")[0] for m in mismatches}
    picked = oracle.sample([d for d in served if d not in bad],
                           t["check"]["sample"], seed)
    items = [(T.prompt_ids(op, served[d].doc, sizes["vocab_size"]),
              tokens[d]) for d in picked]
    numbers = {"failed_requests": float(len(failed) + missing),
               "output_mismatches": float(len(mismatches)),
               "prompt_mismatches": float(prompt_mismatches)}
    readings: Dict[str, Any] = {}
    t_ref = now()
    if items and check:
        readings = oracle.gaps(fam, sizes, seed, items,
                               t["check"]["block"], control=control)
        numbers["max_logit_gap"] = readings["max_logit_gap"]
    else:
        numbers["max_logit_gap"] = float("inf")
    correct, checks = oracle.judge(numbers, limits)
    verdicts = {}
    if "control_max_logit_gap" in readings:
        # the control in the program's place: its gap, the run's exact checks
        ok, ctl = oracle.judge(
            dict(numbers, max_logit_gap=readings["control_max_logit_gap"]),
            limits)
        verdicts["control"] = {"correct": bool(ok), "checks": ctl}
    timings = {"setup_s": setup_s, "window_s": w1 - w0,
               "close_s": t_ref - w1, "reference_s": now() - t_ref}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": len(failed) + missing, "metrics": metrics,
              "device": device}
    if run.trace is not None:
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    return {"result": result, "readings": readings, **verdicts,
            "mismatches": mismatches[:20], "compiles_in_window": compiles,
            "lateness": lateness(run.in_window() or run.reqs),
            "sampled": picked, "timings": timings, "run": run}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    res = out["result"]
    print(f"lateness of the load generator: {json.dumps(out['lateness'])}")
    c = out["compiles_in_window"]
    print(f"programs compiled inside the window: "
          f"{c['backend_compile'] - c['cache_hit']} (and "
          f"{c['cache_hit']} fetched from the compile cache)")
    print(f"timings: {json.dumps(out['timings'])}")
    run = out["run"]
    w0 = run.window[0]
    print("submits (start s, end s from the window's start, size): "
          + json.dumps([[a - w0, b - w0, n] for a, b, n in run.chunks]))
    print("completions (s from the window's start): " + json.dumps(
        sorted(r.done_at - w0 for r in run.reqs if r.done_at is not None)))
    for line in out["mismatches"]:
        print(f"mismatch: {line}")
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
