"""The jitted, sharded step of one (arch x shape) cell on a mesh.

Unlike ``launch/dryrun.py``, which sets ``XLA_FLAGS`` for its
512-device placeholder mesh when it is imported, this module touches no
device state at import.  The dry run lowers the step built here from
shapes alone; a run on real devices initializes arrays with the same
shardings and calls it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import ShardingRules, active_rules
from repro.models.model import build_model
from repro.models.params import split_params
from repro.optim.optimizer import OptimizerConfig, adamw_init, optimizer_state_axes
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train.train_step import make_train_step
from repro.tuning.parameters import BackendConfig

_METRIC_KEYS = ("loss", "ce", "aux", "lr", "grad_norm", "clip", "loss_out")


def eval_shape_with_axes(init_fn):
    """eval_shape a P-pytree init function: returns (value structs, axes).

    The logical-axes tree (static strings) is captured via a side channel
    during the abstract trace so nothing is ever allocated."""
    box = {}

    def values_only():
        values, axes = split_params(init_fn())
        box["axes"] = axes
        return values

    struct = jax.eval_shape(values_only)
    return struct, box["axes"]


@dataclass
class CellStep:
    """A cell's jitted step with its argument shapes and shardings.

    ``structs`` and ``shardings`` are parallel tuples, one pytree per
    positional argument of ``jitted``: (params, opt_state, batch) for a
    train cell, (params, batch, cache) for prefill and (params, tokens,
    cache) for decode.  ``opt_cfg`` is set for train cells."""

    jitted: Callable
    structs: Tuple[Any, ...]
    shardings: Tuple[Any, ...]
    opt_cfg: Any = None

    def lower(self):
        return self.jitted.lower(*self.structs)


def _under_rules(fn, rules: ShardingRules):
    """Trace ``fn`` with ``rules`` active, so the model's activation
    sharding hints resolve against this mesh whenever jit traces it."""

    def traced(*args):
        with active_rules(rules):
            return fn(*args)

    return traced


def build_cell_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                    bc: BackendConfig) -> CellStep:
    model = build_model(cfg)
    rt = bc.runtime()
    overrides = None
    if bc.cache_shard == "heads":
        # decode attention locality: shard the KV cache by kv-heads instead
        # of seq (keeps attention shard-local; no per-token KV all-gather)
        overrides = {"cache_seq": None}
    rules = ShardingRules(mesh, bc.sharding_style, overrides=overrides)
    replicated = NamedSharding(mesh, PartitionSpec())

    params_struct, params_axes = eval_shape_with_axes(
        lambda: model.init(jax.random.PRNGKey(0))
    )
    if shape.kind != "train" and bc.serve_bf16_params:
        # beyond-paper: serve from pre-cast bf16 weights (halves weight HBM
        # and the per-token weight traffic of decode)
        params_struct = jax.tree_util.tree_map(
            lambda st: jax.ShapeDtypeStruct(
                st.shape, jnp.bfloat16 if st.dtype == jnp.float32 else st.dtype
            ),
            params_struct,
        )
    params_sh = rules.tree_shardings(params_axes, params_struct)

    specs = model.input_specs(shape)
    batch_struct = {k: v.struct for k, v in specs.items()}
    batch_sh = {
        k: rules.sharding_for(v.logical_axes, v.struct.shape)
        for k, v in specs.items()
    }

    if shape.kind == "train":
        opt_cfg = OptimizerConfig(
            state_dtype=bc.opt_state_dtype, factored=bc.factored_opt
        )
        opt_struct = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_struct)
        opt_axes = optimizer_state_axes(params_axes, opt_cfg, params_struct)
        opt_sh = rules.tree_shardings(opt_axes, opt_struct)
        step = make_train_step(model, opt_cfg, rt, microbatches=bc.microbatches)
        metrics_sh = {k: replicated for k in _METRIC_KEYS}
        jitted = jax.jit(
            _under_rules(step, rules),
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, metrics_sh),
            donate_argnums=(0, 1),
        )
        return CellStep(jitted, (params_struct, opt_struct, batch_struct),
                        (params_sh, opt_sh, batch_sh), opt_cfg)

    cache_struct, cache_axes = eval_shape_with_axes(
        lambda: model.init_cache(shape.global_batch, shape.seq_len)
    )
    cache_sh = rules.tree_shardings(cache_axes, cache_struct)
    B, V = shape.global_batch, cfg.padded_vocab
    logits_sh = rules.sharding_for(("batch", None, "vocab"), (B, 1, V))
    if shape.kind == "prefill":
        step, inputs = make_prefill_step(model, rt), (batch_struct, batch_sh)
    else:  # decode
        step = make_decode_step(model, rt)
        inputs = (batch_struct["tokens"], batch_sh["tokens"])
    jitted = jax.jit(
        _under_rules(step, rules),
        in_shardings=(params_sh, inputs[1], cache_sh),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(2,),
    )
    return CellStep(jitted, (params_struct, inputs[0], cache_struct),
                    (params_sh, inputs[1], cache_sh))


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, bc: BackendConfig):
    """Lower one cell from shapes alone (nothing is allocated)."""
    return build_cell_step(cfg, shape, mesh, bc).lower()
