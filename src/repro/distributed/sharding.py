"""Logical-axis sharding rules.

Parameters and activations carry *logical* axis names ("embed", "ff",
"heads", "vocab", "experts", "batch", "seq", ...).  A ``ShardingRules``
maps logical names to mesh axis names, dropping any assignment whose
dimension is not divisible by the mesh-axis size (e.g. qwen2's 14 heads on
a 16-way model axis are replicated rather than unevenly sharded).

Two rule families (both tunable by the paper-style tuner):

* ``tp``      — pure tensor-parallel: params shard over "model" only; the
                "data"/"pod" axes carry batch (classic DP+TP).
* ``fsdp_tp`` — additionally shards the params' "embed" dimension over
                "data" (ZeRO-3/FSDP style; XLA inserts the all-gathers).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.optim.optimizer import OptimizerConfig, adamw_init, optimizer_state_axes


# logical name -> candidate mesh axes (first whose size divides the dim wins;
# a tuple value means "shard over these mesh axes jointly").
def make_rules(style: str, multi_pod: bool) -> dict:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    rules = {
        "batch": batch_axes,
        "seq": ("model",),  # activations' seq dim: only for long-context/SP
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "experts": ("model",),
        "cache_seq": ("model",),
        "state": ("model",),
        "layers": None,
        "head": None,
        "lora": None,
        "embed": ("data",) if style == "fsdp_tp" else None,
    }
    if style not in ("tp", "fsdp_tp"):
        raise ValueError(f"unknown sharding style {style!r}")
    return rules


class ShardingRules:
    def __init__(self, mesh: Mesh, style: str = "fsdp_tp", overrides: Optional[dict] = None):
        self.mesh = mesh
        self.style = style
        multi_pod = "pod" in mesh.axis_names
        self.rules = make_rules(style, multi_pod)
        if overrides:
            self.rules.update(overrides)

    def _axis_size(self, axis) -> int:
        if isinstance(axis, tuple):
            return int(np.prod([self.mesh.shape[a] for a in axis]))
        return int(self.mesh.shape[axis])

    def spec_for(
        self, logical_axes: Sequence[Optional[str]], shape: Optional[Tuple[int, ...]] = None
    ) -> PartitionSpec:
        """Resolve logical axes -> PartitionSpec, honouring divisibility."""
        out = []
        used: set = set()
        for i, name in enumerate(logical_axes):
            assignment = None
            if name is not None:
                cand = self.rules.get(name)
                if cand is not None:
                    flat = cand if isinstance(cand, tuple) else (cand,)
                    # skip axes already used by another dim of this array
                    if not (set(flat) & used):
                        size = self._axis_size(cand)
                        if shape is None or shape[i] % size == 0:
                            # bare name for single axes: P("data"), not
                            # P(("data",)) — newer jax treats them as distinct
                            assignment = flat[0] if len(flat) == 1 else cand
                            used.update(flat)
            out.append(assignment)
        while out and out[-1] is None:
            out.pop()
        return PartitionSpec(*out)

    def sharding_for(self, logical_axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes, shape))

    def tree_specs(self, axes_tree, values_tree):
        """PartitionSpec pytree parallel to a params pytree."""
        return jax.tree_util.tree_map(
            lambda axes, v: self.spec_for(axes, tuple(v.shape)),
            axes_tree,
            values_tree,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(a, (str, type(None))) for a in x),
        )

    def tree_shardings(self, axes_tree, values_tree):
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.tree_specs(axes_tree, values_tree),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )


@dataclass(frozen=True)
class StepShardings:
    """Where a step's arguments and results live on the rules' mesh.

    ``params`` and ``opt`` are pytrees parallel to the parameters and the
    AdamW state (``opt`` is None for a step without one), ``batch`` maps
    each input's name to its sharding, and ``metrics`` (replicated) is a
    prefix of the step's metrics dict."""

    params: Any
    opt: Any
    batch: Dict[str, NamedSharding]
    metrics: NamedSharding


def step_shardings(rules: ShardingRules, params_axes, params_struct,
                   batch_specs: Dict[str, Any],
                   opt_cfg: Optional[OptimizerConfig] = None) -> StepShardings:
    """The one construction of a step's shardings from (mesh, style), used
    by the tuner's cell step and by the train loop. ``batch_specs`` maps
    input names to ``InputSpec``s (a struct and its logical axes)."""
    opt = None
    if opt_cfg is not None:
        opt_struct = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_struct)
        opt = rules.tree_shardings(
            optimizer_state_axes(params_axes, opt_cfg, params_struct), opt_struct)
    return StepShardings(
        params=rules.tree_shardings(params_axes, params_struct),
        opt=opt,
        batch={k: rules.sharding_for(v.logical_axes, v.struct.shape)
               for k, v in batch_specs.items()},
        metrics=NamedSharding(rules.mesh, PartitionSpec()))


# ---------------------------------------------------------------------------
# Activation sharding hints inside model code
# ---------------------------------------------------------------------------

_ACTIVE = threading.local()


@contextlib.contextmanager
def active_rules(rules: Optional[ShardingRules]):
    prev = getattr(_ACTIVE, "rules", None)
    _ACTIVE.rules = rules
    try:
        yield
    finally:
        _ACTIVE.rules = prev


def current_rules() -> Optional[ShardingRules]:
    """The rules active on this thread, or None outside a distributed
    context."""
    return getattr(_ACTIVE, "rules", None)


def under_rules(fn, rules: ShardingRules):
    """``fn`` traced with ``rules`` active, so the model's activation
    sharding hints resolve against this mesh whenever jit traces it."""

    def traced(*args):
        with active_rules(rules):
            return fn(*args)

    return traced


def shard_hint(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint against the active rules; no-op outside a
    distributed context (CPU smoke tests)."""
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.spec_for(logical_axes, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
