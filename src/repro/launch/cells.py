"""Cell builder: (architecture x input shape x mesh) -> lowerable closure.

A *cell* is one entry of the assigned grid. ``build_cell`` assembles the
step function (train_step / prefill / serve_step), abstract inputs
(ShapeDtypeStruct only — nothing is allocated), and in/out shardings, ready
for ``jax.jit(...).lower(...).compile()`` in the dry-run.

Per-arch run profiles carry the §Perf knobs (microbatch count, remat,
sharding-policy overrides); hillclimb iterations override them via
``profile_overrides`` / ``policy_overrides``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.shapes import SHAPES, ShapeSpec, cache_len_for, skip_reason
from repro.launch import sharding as shd
from repro.models import api
from repro.models.config import ModelConfig
from repro.serving.decode import make_serve_step
from repro.training.train_step import TrainHyper, make_opt_init, \
    make_train_step

# ---------------------------------------------------------------------------
# per-arch run profiles (baseline §Perf knobs)
# ---------------------------------------------------------------------------

RUN_PROFILES: Dict[str, Dict[str, Any]] = {
    "grok-1-314b": dict(microbatches=16, remat="full",
                        optimizer="adafactor", grad_dtype="bfloat16"),
    "granite-34b": dict(microbatches=16, remat="full"),
    "gemma3-27b": dict(microbatches=16, remat="full"),
    "gemma2-9b": dict(microbatches=8, remat="full"),
    "zamba2-2.7b": dict(microbatches=4, remat="full"),
    "whisper-medium": dict(microbatches=4, remat="full"),
    "mamba2-370m": dict(microbatches=2, remat="full"),
    "llama3.2-1b": dict(microbatches=2, remat="full"),
    "granite-moe-1b-a400m": dict(microbatches=2, remat="full"),
    "internvl2-1b": dict(microbatches=2, remat="full"),
}


# confirmed §Perf wins (see EXPERIMENTS.md §Perf), applied by the
# --optimized dry-run on top of the baseline RUN_PROFILES. Deliberately
# TARGETED per arch: the first blanket application regressed cells the
# optimizations were not diagnosed on (granite-moe train 0.44x under
# tp_min64) — §Perf "optimized vs baseline" documents the lesson.
OPTIMIZED_POLICY: Dict[str, Dict[str, Any]] = {
    # tp_min64 strips the resharding storm; seq_parallel then re-employs
    # the idle model axis (safe exactly because attention is un-TP'd here)
    "internvl2-1b": {"tp_min_shard": 64, "seq_parallel": True},
}
OPTIMIZED_CONFIG: Dict[str, Dict[str, Any]] = {
    "grok-1-314b": {"kv_cache_dtype": "int8"},
    # int8 KV for the caches that crowd HBM at decode (gemma2 decode 83%,
    # long_500k 99%; zamba2 decode 92%) — ~1% rel logit error, top-1 stable
    "gemma2-9b": {"kv_cache_dtype": "int8"},
    "zamba2-2.7b": {"kv_cache_dtype": "int8"},
}


def set_optimized_flags(on: bool = True):
    """Module-level §Perf switches (exact-math rewrites)."""
    import repro.models.attention as A
    A.GROUPED_DECODE_ATTENTION = on
    A.WINDOWED_CHUNK_ATTENTION = on


@dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Callable
    args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    meta: Dict[str, Any] = field(default_factory=dict)
    act_batch_axes: Tuple[str, ...] = ()
    act_seq_axes: Tuple[str, ...] = ()
    axis_sizes: Dict[str, int] = field(default_factory=dict)

    def lower(self, mesh: Mesh):
        from repro.models.partitioning import activation_sharding
        with mesh, activation_sharding(self.act_batch_axes,
                                       self.act_seq_axes or None,
                                       self.axis_sizes):
            jitted = jax.jit(
                self.fn,
                in_shardings=self.in_shardings,
                out_shardings=self.out_shardings,
                donate_argnums=self.donate_argnums,
            )
            return jitted.lower(*self.args)


def _named(mesh: Mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _input_struct(cfg: ModelConfig, spec: ShapeSpec) -> Dict[str, Any]:
    """Abstract model inputs for one batch of this shape (train/prefill)."""
    b, s = spec.global_batch, spec.seq_len
    inputs: Dict[str, Any] = {}
    if cfg.family == "vlm":
        s_text = s - cfg.num_patches
        inputs["tokens"] = jax.ShapeDtypeStruct((b, s_text), jnp.int32)
        vd = cfg.vit_dim or cfg.d_model
        inputs["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.num_patches, vd), jnp.float32)
    else:
        inputs["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if cfg.is_encoder_decoder:
        inputs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    return inputs


def _params_struct(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg))


def build_cell(
    mesh: Mesh,
    arch: str,
    shape_name: str,
    *,
    profile_overrides: Optional[Dict[str, Any]] = None,
    policy_overrides: Optional[Dict[str, Any]] = None,
    config_overrides: Optional[Dict[str, Any]] = None,
) -> Cell:
    spec = SHAPES[shape_name]
    reason = skip_reason(get_config(arch), shape_name)
    if reason:
        raise ValueError(f"cell ({arch}, {shape_name}) skipped: {reason}")

    profile = dict(RUN_PROFILES.get(arch, {}))
    profile.update(profile_overrides or {})
    cfg = get_config(arch)
    if spec.kind == "train":
        cfg = cfg.replace(remat=profile.get("remat", "none"))
    if config_overrides:
        cfg = cfg.replace(**config_overrides)

    pol = shd.policy_for(mesh, cfg, kind=spec.kind, batch=spec.global_batch,
                         **(policy_overrides or {}))

    params_struct = _params_struct(cfg)
    param_specs = shd.param_pspecs(cfg, params_struct, pol)

    meta = dict(arch=arch, shape=shape_name, kind=spec.kind,
                global_batch=spec.global_batch, seq_len=spec.seq_len,
                n_devices=mesh.devices.size, profile=profile)

    if spec.kind == "train":
        # elastic-scaling guard (caught by the multi-pod dry-run): the
        # per-microbatch batch must still cover every data shard, or the
        # microbatch activations replicate across the starved shards
        batch_shards = pol.size(pol.data_axes)
        max_mb = max(1, spec.global_batch // batch_shards)
        hyper = TrainHyper(
            microbatches=min(profile.get("microbatches", 1), max_mb),
            grad_dtype=profile.get("grad_dtype", "float32"),
            optimizer=profile.get("optimizer", "adamw"),
        )
        fn = make_train_step(cfg, hyper, data_axes=pol.data_axes)
        opt_struct = jax.eval_shape(make_opt_init(hyper), params_struct)
        opt_specs = shd.opt_pspecs(cfg, opt_struct, param_specs)
        batch_struct = _input_struct(cfg, spec)
        tok_shape = batch_struct["tokens"].shape
        batch_struct["labels"] = jax.ShapeDtypeStruct(tok_shape, jnp.int32)
        batch_specs = shd.batch_pspecs(cfg, batch_struct, pol)
        # metrics: replicated scalars (eval under the mesh context — the
        # microbatch split applies a with_sharding_constraint)
        from repro.models.partitioning import activation_sharding
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        seq_axes = pol.model_axes if pol.seq_parallel else ()
        with mesh, activation_sharding(pol.data_axes, seq_axes or None,
                                       axis_sizes):
            metrics_struct = jax.eval_shape(fn, params_struct, opt_struct,
                                            batch_struct)[2]
        metrics_specs = jax.tree.map(lambda _: P(), metrics_struct)
        meta["tokens_per_step"] = int(tok_shape[0] * tok_shape[1])
        meta["microbatches"] = hyper.microbatches
        return Cell(
            arch, shape_name, "train", fn,
            args=(params_struct, opt_struct, batch_struct),
            in_shardings=(_named(mesh, param_specs), _named(mesh, opt_specs),
                          _named(mesh, batch_specs)),
            out_shardings=(_named(mesh, param_specs), _named(mesh, opt_specs),
                           _named(mesh, metrics_specs)),
            donate_argnums=(0, 1),
            meta=meta,
            act_batch_axes=pol.data_axes,
            act_seq_axes=seq_axes,
            axis_sizes=axis_sizes,
        )

    if spec.kind == "prefill":
        max_len = spec.seq_len + 128

        def prefill_fn(params, inputs):
            return api.prefill(params, cfg, max_len, **inputs)

        inputs_struct = _input_struct(cfg, spec)
        inputs_specs = shd.batch_pspecs(cfg, inputs_struct, pol)
        out_struct = jax.eval_shape(prefill_fn, params_struct, inputs_struct)
        logits_spec = P(shd._spec_entry(spec.global_batch, pol.data_axes, pol),
                        None, None)
        cache_specs = shd.cache_pspecs(cfg, out_struct[1], pol)
        meta["tokens_per_step"] = spec.global_batch * spec.seq_len
        return Cell(
            arch, shape_name, "prefill", prefill_fn,
            args=(params_struct, inputs_struct),
            in_shardings=(_named(mesh, param_specs),
                          _named(mesh, inputs_specs)),
            out_shardings=(NamedSharding(mesh, logits_spec),
                           _named(mesh, cache_specs)),
            donate_argnums=(),
            meta=meta,
            act_batch_axes=pol.data_axes,
            act_seq_axes=(pol.model_axes if pol.seq_parallel else ()),
            axis_sizes=dict(zip(mesh.axis_names, mesh.devices.shape)),
        )

    # decode
    b = spec.global_batch
    cache_len = cache_len_for(cfg, spec)
    cache_struct = jax.eval_shape(
        lambda: api.init_cache(cfg, b, cache_len))
    # pretend the cache is full up to seq_len (the assigned cell semantics:
    # one new token against a seq_len-token cache)
    cache_specs = shd.cache_pspecs(cfg, cache_struct, pol)
    token_struct = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    token_spec = P(shd._spec_entry(b, pol.data_axes, pol), None)
    serve_step = make_serve_step(cfg)

    def serve_fn(params, token, cache):
        return serve_step(params, token, cache)

    meta["tokens_per_step"] = b
    meta["cache_len"] = cache_len
    return Cell(
        arch, shape_name, "decode", serve_fn,
        args=(params_struct, token_struct, cache_struct),
        in_shardings=(_named(mesh, param_specs),
                      NamedSharding(mesh, token_spec),
                      _named(mesh, cache_specs)),
        out_shardings=(NamedSharding(mesh, token_spec),
                       _named(mesh, cache_specs)),
        donate_argnums=(2,),
        meta=meta,
        act_batch_axes=pol.data_axes,
        act_seq_axes=(),
        axis_sizes=dict(zip(mesh.axis_names, mesh.devices.shape)),
    )
