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

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import ShardingRules, step_shardings, under_rules
from repro.models.model import build_model
from repro.models.params import eval_shape_with_axes
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train.train_step import make_train_step
from repro.tuning.parameters import BackendConfig


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
    specs = model.input_specs(shape)
    batch_struct = {k: v.struct for k, v in specs.items()}
    opt_cfg = (OptimizerConfig(state_dtype=bc.opt_state_dtype,
                               factored=bc.factored_opt)
               if shape.kind == "train" else None)
    sh = step_shardings(rules, params_axes, params_struct, specs, opt_cfg)
    params_sh, batch_sh = sh.params, sh.batch

    if shape.kind == "train":
        opt_struct = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_struct)
        step = make_train_step(model, opt_cfg, rt, microbatches=bc.microbatches)
        jitted = jax.jit(
            under_rules(step, rules),
            in_shardings=(params_sh, sh.opt, batch_sh),
            out_shardings=(params_sh, sh.opt, sh.metrics),
            donate_argnums=(0, 1),
        )
        return CellStep(jitted, (params_struct, opt_struct, batch_struct),
                        (params_sh, sh.opt, batch_sh), opt_cfg)

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
        under_rules(step, rules),
        in_shardings=(params_sh, inputs[1], cache_sh),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(2,),
    )
    return CellStep(jitted, (params_struct, inputs[0], cache_struct),
                    (params_sh, inputs[1], cache_sh))


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, bc: BackendConfig):
    """Lower one cell from shapes alone (nothing is allocated)."""
    return build_cell_step(cfg, shape, mesh, bc).lower()
