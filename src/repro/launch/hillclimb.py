import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> measure.

Runs named experiment variants of a (arch x shape) cell against the
production mesh, printing the three roofline terms and the deltas vs the
cell's baseline artifact. Results append to artifacts/perf/<cell>.jsonl so
EXPERIMENTS.md §Perf can cite exact numbers.

  PYTHONPATH=src python -m repro.launch.hillclimb --cell gemma2-9b:train_4k \
      --exp windowed_attention
  PYTHONPATH=src python -m repro.launch.hillclimb --list
"""

import argparse
import json
from typing import Any, Callable, Dict, Optional

import jax  # noqa: F401 — initialize under XLA_FLAGS before model code

from repro.launch.dryrun import run_cell
from repro.launch.mesh import make_production_mesh


class Experiment:
    def __init__(self, name: str, hypothesis: str,
                 profile: Optional[Dict[str, Any]] = None,
                 policy: Optional[Dict[str, Any]] = None,
                 config: Optional[Dict[str, Any]] = None,
                 setup: Optional[Callable[[], None]] = None,
                 teardown: Optional[Callable[[], None]] = None):
        self.name = name
        self.hypothesis = hypothesis
        self.profile = profile or {}
        self.policy = policy or {}
        self.config = config or {}
        self.setup = setup
        self.teardown = teardown


def _flag_windowed(on: bool):
    def f():
        import repro.models.attention as A
        A.WINDOWED_CHUNK_ATTENTION = on
    return f


def _flag_grouped(on: bool):
    def f():
        import repro.models.attention as A
        A.GROUPED_DECODE_ATTENTION = on
    return f


EXPERIMENTS = {
    # --- memory/compute term: attention ---
    "windowed_attention": Experiment(
        "windowed_attention",
        "local-attention layers slice K/V to the (window+chunk) band "
        "instead of masking full-S scores: local-layer attention FLOPs and "
        "score-tensor bytes drop ~S/(window+chunk)x",
        setup=_flag_windowed(True), teardown=_flag_windowed(False)),
    # --- sequence parallelism ---
    "seq_parallel": Experiment(
        "seq_parallel",
        "shard the residual stream's token dim over 'model' between blocks: "
        "stored remat checkpoints and layer-boundary activation traffic "
        "shrink ~16x at the price of per-block all-gather/reduce-scatter",
        policy={"seq_parallel": True}),
    # --- microbatching ---
    "microbatch_8": Experiment(
        "microbatch_8",
        "halving microbatches (16->8) halves the number of FSDP weight "
        "all-gather sweeps per step; activation memory doubles",
        profile={"microbatches": 8}),
    "microbatch_4": Experiment(
        "microbatch_4", "mb 8->4, same hypothesis",
        profile={"microbatches": 4}),
    "microbatch_1": Experiment(
        "microbatch_1",
        "single pass: minimal weight-gather traffic, maximal activations",
        profile={"microbatches": 1}),
    # --- remat ---
    "no_remat": Experiment(
        "no_remat",
        "activation checkpointing off: ~25-33% of compiled FLOPs are remat "
        "recompute; small models can afford the activation memory",
        profile={"remat": "none"}),
    "grouped_decode": Experiment(
        "grouped_decode",
        "decode attention grouped by kv-head (no jnp.repeat KV expansion) "
        "lets GSPMD propagate the cache sharding into a distributed "
        "softmax: removes the per-layer full-cache all-gather + the GQA "
        "expansion copies",
        setup=_flag_grouped(True), teardown=_flag_grouped(False)),
    "tp_min64": Experiment(
        "tp_min64",
        "skip model-axis TP on projections whose per-device shard would be "
        "<64 wide (internvl2 kv proj = 128/16 = 8): the tiny shards force "
        "involuntary resharding (replicate+slice) per layer",
        policy={"tp_min_shard": 64}),
    "tp_min64_seqpar": Experiment(
        "tp_min64_seqpar",
        "on top of tp_min64 (attention un-TP'd), shard the residual "
        "sequence over 'model' so the idle model axis works on tokens: "
        "compute overhead of tp_min64 should revert, at small collective "
        "cost (per-block all-gather/reduce-scatter)",
        policy={"tp_min_shard": 64, "seq_parallel": True}),
    # --- decode/serving shardings ---
    "params_model_only": Experiment(
        "params_model_only",
        "decode: shard params over 'model' only (no FSDP) when they fit "
        "HBM — removes the per-step weight all-gather over 'data'",
        policy={"shard_params_data": False}),
    "cache_seq_sharded": Experiment(
        "cache_seq_sharded",
        "decode: shard the KV cache over sequence instead of kv-heads "
        "(adds softmax partial-reductions, removes head-dim constraints)",
        policy={"cache_layout": "seq"}),
    "cache_heads_sharded": Experiment(
        "cache_heads_sharded", "inverse of cache_seq_sharded",
        policy={"cache_layout": "heads"}),
    "kv_int8": Experiment(
        "kv_int8",
        "int8 KV cache (per-token-head absmax scales): cache capacity and "
        "cache-read traffic halve; dequant fuses into the attention dot on "
        "TPU (CPU HLO shows a separate fusion, limiting the measured "
        "traffic gain to the capacity axis)",
        config={"kv_cache_dtype": "int8"}),
    # --- grad compression ---
    "grad_bf16": Experiment(
        "grad_bf16",
        "bf16 gradient accumulation halves accumulator memory and any "
        "fp32 grad collectives",
        profile={"grad_dtype": "bfloat16"}),
}


def run_experiment(arch: str, shape: str, exp_name: str,
                   multi_pod: bool = False):
    exp = EXPERIMENTS[exp_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_label = "pod2x16x16" if multi_pod else "pod16x16"
    print(f"### experiment {exp_name} on {arch} x {shape}")
    print(f"    hypothesis: {exp.hypothesis}")

    baseline_path = os.path.join("artifacts/dryrun", mesh_label,
                                 f"{arch}__{shape}.json")
    baseline = None
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f)

    if exp.setup:
        exp.setup()
    try:
        res = run_cell(mesh, mesh_label, arch, shape, None,
                       profile_overrides=exp.profile or None,
                       policy_overrides=exp.policy or None,
                       config_overrides=exp.config or None)
    finally:
        if exp.teardown:
            exp.teardown()
    rep = res["report"]

    row = {"cell": f"{arch}:{shape}", "mesh": mesh_label, "exp": exp_name,
           "hypothesis": exp.hypothesis, **rep.to_json()}
    if baseline and baseline.get("status") == "ok":
        for k in ("compute_s", "memory_s", "collective_s", "temp_bytes",
                  "flops", "bytes_accessed", "collective_bytes"):
            base = baseline.get(k, 0.0)
            if base:
                row[f"delta_{k}"] = (rep.to_json()[k] - base) / base
        print("    deltas vs baseline: " + "  ".join(
            f"{k.split('_', 1)[1]}={100 * v:+.1f}%"
            for k, v in row.items() if k.startswith("delta_")))
    os.makedirs("artifacts/perf", exist_ok=True)
    with open(os.path.join("artifacts/perf", f"{arch}__{shape}.jsonl"),
              "a") as f:
        f.write(json.dumps(row) + "\n")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch:shape")
    ap.add_argument("--exp", help="experiment name (comma separated)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list:
        for name, e in EXPERIMENTS.items():
            print(f"{name:22s} {e.hypothesis}")
        return
    arch, shape = args.cell.split(":")
    for exp in args.exp.split(","):
        run_experiment(arch, shape, exp.strip(), multi_pod=args.multi_pod)


if __name__ == "__main__":
    main()
