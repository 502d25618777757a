"""The float32 reference of :mod:`bench.reference`, run over several chips.

A model whose float32 training state does not fit one chip (the whole
h2o-danube-1.8b: 16 bytes a parameter, 29 GB) is checked against the same
mathematics, split over the cell's chips. Everything that computes comes
from :mod:`bench.reference`: the weights (:func:`~bench.reference.init_params`),
the loss (:func:`~bench.reference.loss_fn`), AdamW
(:func:`~bench.reference.adamw`), the readings
(:func:`~bench.reference.leaf_norms`) and the batches
(:func:`~bench.reference.token_batch`, called by the driver). This module
only places them:

* every leaf stacked over layers is split along its first axis after the
  layer axis that the chip count divides, so the scan over layers slices
  each layer's weights locally and never gathers the whole stack;
* the embedding and the head are split along the vocabulary; the final
  norm's scale is held whole on every chip;
* each batch is split along its rows, where the chip count divides them.

The compiler partitions the rest. Matrix products run at
``Precision.HIGHEST`` as in :mod:`bench.reference`. Like it, this module
imports nothing of the program.
"""
from __future__ import annotations

import json
import threading
from concurrent.futures import Future
from typing import Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench.reference import (adamw, flat_norms, init_params, leaf_norms, loss_fn,
                             make_dot)

AXIS = "chips"


def leaf_spec(name: str, shape, n: int) -> PartitionSpec:
    """Where a parameter (or its moment) named ``name`` is split."""
    if "['blocks']" in name:
        for ax in range(1, len(shape)):
            if shape[ax] % n == 0:
                return PartitionSpec(*([None] * ax), AXIS)
    elif name == "['embed']" and shape[0] % n == 0:
        return PartitionSpec(AXIS)
    elif name == "['head']" and shape[1] % n == 0:
        return PartitionSpec(None, AXIS)
    return PartitionSpec()


def _config(m: dict) -> dict:
    """The configuration's numbers, without its notes."""
    return {k: v for k, v in m.items() if not isinstance(v, (dict, list))}


def _compile(m: dict, opt: dict, shape, devices, numerics, fault):
    """The reference's four programs, compiled for ``devices``."""
    mesh = Mesh(np.asarray(devices), (AXIS,))
    n = len(devices)
    struct = jax.eval_shape(lambda s: init_params(m, s), 0)
    p_sh = jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(
            mesh, leaf_spec(jax.tree_util.keystr(path), x.shape, n)), struct)
    rep = NamedSharding(mesh, PartitionSpec())
    s_sh = {"m": p_sh, "v": p_sh, "count": rep}
    b_sh = NamedSharding(mesh, PartitionSpec(AXIS if shape[0] % n == 0 else None))
    dot = make_dot(numerics)

    def init(seed):
        return jax.lax.with_sharding_constraint(init_params(m, seed), p_sh)

    def zeros(params):
        return {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
                "v": jax.tree_util.tree_map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}

    def step(params, state, tokens, targets):
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, targets = tokens[:half], targets[:half]
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, m, dot)
        params, state = adamw(params, grads, state, opt)
        return params, state, loss, leaf_norms(state["m"])

    def change(params, seed):
        return leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, init(seed)))

    seed = jax.ShapeDtypeStruct((), jnp.int32)
    params = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                    struct)
    state = jax.eval_shape(zeros, params)
    tokens = jax.ShapeDtypeStruct(tuple(shape), jnp.int32)
    with jax.default_matmul_precision("highest"):
        return Programs(
            jax.jit(init, out_shardings=p_sh).lower(seed).compile(),
            jax.jit(zeros, in_shardings=(p_sh,), out_shardings=s_sh
                    ).lower(params).compile(),
            jax.jit(step, in_shardings=(p_sh, s_sh, b_sh, b_sh),
                    out_shardings=(p_sh, s_sh, rep, rep), donate_argnums=(0, 1)
                    ).lower(params, state, tokens, tokens).compile(),
            jax.jit(change, in_shardings=(p_sh, rep), out_shardings=rep
                    ).lower(params, seed).compile(),
            b_sh)


class Programs(NamedTuple):
    init: Callable
    zeros: Callable
    step: Callable
    change: Callable
    batch_sharding: NamedSharding


_COMPILED: Dict[tuple, Future] = {}
_LOCK = threading.Lock()


def programs(m: dict, opt: dict, shape, devices: Sequence, *,
             numerics: str = "f32", fault: Optional[str] = None) -> Programs:
    """The reference's programs for batches of ``shape`` (rows, tokens) over
    ``devices``, compiled once a process. Threads may ask at once (the
    compiler then works on several while the chips run other work); each
    program compiles once."""
    if opt.get("factored") or opt.get("state_dtype", "f32") != "f32":
        raise ValueError("the reference's AdamW keeps float32, unfactored moments")
    if fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    m = _config(m)
    opt = {k: v for k, v in opt.items() if k not in ("factored", "state_dtype")}
    key = (json.dumps([m, opt], sort_keys=True), tuple(shape), tuple(devices),
           numerics, fault)
    with _LOCK:
        built = _COMPILED.get(key)
        mine = built is None
        if mine:
            built = _COMPILED[key] = Future()
    if mine:
        try:
            built.set_result(_compile(m, opt, shape, devices, numerics, fault))
        except BaseException as e:
            built.set_exception(e)
    return built.result()


def lm_readings(m: dict, opt: dict, batches, seed: int, devices: Sequence, *,
                numerics: str = "f32", fault: Optional[str] = None) -> dict:
    """:func:`bench.reference.lm_readings` over ``devices``: the same
    ``{"loss", "grad", "change"}`` readings of ``len(batches)`` AdamW steps
    from the seed's weights."""
    run = programs(m, opt, np.shape(batches[0]["tokens"]), devices,
                   numerics=numerics, fault=fault)
    seed = np.int32(seed)
    params = run.init(seed)
    state = run.zeros(params)
    losses, grad = [], None
    for i, b in enumerate(batches):
        tokens, targets = (jax.device_put(b[k], run.batch_sharding)
                           for k in ("tokens", "targets"))
        params, state, loss, mn = run.step(params, state, tokens, targets)
        losses.append(float(loss))
        if i == 0:
            grad = {k: v / (1 - opt["beta1"])
                    for k, v in flat_norms(jax.device_get(mn)).items()}
    del state
    moved = flat_norms(jax.device_get(run.change(params, seed)))
    return {"loss": losses, "grad": grad, "change": moved}
