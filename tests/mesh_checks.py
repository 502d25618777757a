"""Checks of the train loop on a 2x2 mesh against one device, run as a
separate process on four virtual CPU devices (``tests/test_mesh_train.py``
starts it with ``--xla_force_host_platform_device_count=4``). Prints one
JSON object of readings for the tests to judge.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/mesh_checks.py
"""
import json
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.distributed.sharding import ShardingRules, under_rules
from repro.kernels import ops
from repro.launch import train as train_cli
from repro.launch.mesh import make_mesh
from repro.models.runtime import Runtime
from repro.optim.optimizer import OptimizerConfig
from repro.runtime.fault_tolerance import FailureInjector
from repro.train import trainer as trainer_mod
from repro.train.trainer import Trainer, TrainerConfig

STEPS = 3
SEQ, BATCH = 32, 4
#: danube's shape at a tiny size: GQA 4/2 and a window of 8 under a
#: sequence of 32, so the window masks
CFG = get_config("h2o-danube-1.8b").reduced()
RT = Runtime(attn_impl="pallas", compute_dtype="f32", block_q=16, block_kv=16,
             remat="full")


def _norms(tree):
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(x.astype(jnp.float32)))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _whole_on_a_device(tree, axes):
    """Paths of matrices (two or more axes besides the layer stack) that
    some device holds whole."""
    is_axes = lambda a: isinstance(a, tuple) and all(
        isinstance(n, (str, type(None))) for n in a)
    flat_axes = jax.tree_util.tree_leaves(axes, is_leaf=is_axes)
    whole = []
    for (path, x), ax in zip(jax.tree_util.tree_flatten_with_path(tree)[0], flat_axes):
        if sum(n != "layers" for n in ax) < 2:
            continue
        if any(s.data.shape == x.shape for s in x.addressable_shards):
            whole.append(jax.tree_util.keystr(path))
    return whole


def _whole_state(t):
    """:func:`_whole_on_a_device` over a trainer's parameters and moments."""
    return [p for tree in (t.params, t.opt_state["m"], t.opt_state["v"])
            for p in _whole_on_a_device(tree, t.params_axes)]


OPT = OptimizerConfig(learning_rate=1e-3, warmup_steps=1, total_steps=100)
DATA = DataConfig(vocab_size=CFG.vocab_size, seq_len=SEQ, global_batch=BATCH, seed=11)


def train(mesh):
    t = Trainer(CFG, OPT, DATA, TrainerConfig(steps=1, seed=5, log_every=0),
                rt=RT, mesh=mesh)
    out = {"whole_at_init": [], "whole_after": []}
    if mesh is not None:
        out["whole_at_init"] = _whole_state(t)
    t.run()
    out["grad"] = {k: v / (1 - OPT.beta1) for k, v in _norms(t.opt_state["m"]).items()}
    t.tcfg.steps = STEPS
    t.run()
    out["loss"] = [m["loss"] for m in t.metrics_log]
    out["params"] = {jax.tree_util.keystr(p): np.asarray(x).ravel().tolist()
                     for p, x in jax.tree_util.tree_flatten_with_path(t.params)[0]}
    if mesh is not None:
        out["whole_after"] = _whole_state(t)
    out["collectives"] = dict(trainer_mod.STEP_COLLECTIVES)
    return out


def rollback(mesh):
    """A failure at step 2 on the mesh: restore the step-2 checkpoint into
    the same shardings and train on to step 3."""
    with tempfile.TemporaryDirectory() as tmp:
        t = Trainer(CFG, OPT, DATA,
                    TrainerConfig(steps=STEPS, seed=5, log_every=0,
                                  checkpoint_dir=tmp, checkpoint_every=2),
                    rt=RT, failure_injector=FailureInjector(at_steps=[2]), mesh=mesh)
        t.run()
    return {"events": t.events, "loss": [m["loss"] for m in t.metrics_log],
            "whole": _whole_state(t),
            "same_shardings": all(
                x.sharding == s for x, s in zip(jax.tree_util.tree_leaves(t.params),
                                                jax.tree_util.tree_leaves(t.shardings.params)))}


def attention(mesh):
    """The Pallas forward and backward (interpreted), with and without the
    mesh's rules active."""
    B, S, H, K, dh = 4, 32, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (B, S, H, dh))
    k = jax.random.normal(keys[1], (B, S, K, dh))
    v = jax.random.normal(keys[2], (B, S, K, dh))
    w = jax.random.normal(keys[3], (B, S, H, dh))

    def f(q, k, v):
        o = ops.attention(q, k, v, window=8, impl="pallas", block_q=16, block_kv=16)
        return jnp.sum(o * w), o

    def run(fn):
        (_, o), g = jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2),
                                               has_aux=True))(q, k, v)
        return np.asarray(o), [np.asarray(x) for x in g]

    rules = ShardingRules(mesh, "fsdp_tp")
    o0, g0 = run(f)
    o1, g1 = run(under_rules(f, rules))
    jaxpr = str(jax.make_jaxpr(under_rules(f, rules))(q, k, v))
    return {"fwd_err": float(np.max(np.abs(o1 - o0))),
            "fwd_scale": float(np.max(np.abs(o0))),
            "grad_err": [float(np.max(np.abs(a - b))) for a, b in zip(g1, g0)],
            "grad_scale": [float(np.max(np.abs(b))) for b in g0],
            "shard_map_on_mesh": "shard_map" in jaxpr,
            "shard_map_alone": "shard_map" in str(jax.make_jaxpr(f)(q, k, v))}


def _kernels_by_place(jaxpr, inside=False):
    """Names of the pallas_call kernels in a jaxpr, each marked with whether
    it lies inside a shard_map."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["jaxpr"].debug_info.func_name, inside))
        for p in eqn.params.values():
            for x in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    out += _kernels_by_place(
                        sub, inside or eqn.primitive.name == "shard_map")
    return out


def attention_backward(mesh):
    """The Pallas flash backward per shard: causal GQA at a length that is
    not a multiple of the tile, bf16, under the fsdp_tp rules against one
    device; and where each kernel of the differentiated call runs."""
    B, S, H, K, dh = 4, 40, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, shape).astype(jnp.bfloat16) for kk, shape in
               zip(keys, [(B, S, H, dh), (B, S, K, dh), (B, S, K, dh)]))
    w = jax.random.normal(keys[3], (B, S, H, dh))

    def f(q, k, v):
        o = ops.attention(q, k, v, impl="pallas", block_q=16, block_kv=16)
        return jnp.sum(w * o.astype(jnp.float32))

    grad = jax.grad(f, argnums=(0, 1, 2))
    rules = ShardingRules(mesh, "fsdp_tp")
    g0 = jax.jit(grad)(q, k, v)
    g1 = jax.jit(under_rules(grad, rules))(q, k, v)
    places = _kernels_by_place(jax.make_jaxpr(under_rules(grad, rules))(q, k, v).jaxpr)
    f32 = lambda x: np.asarray(x, np.float32)
    return {"grad_err": [float(np.max(np.abs(f32(a) - f32(b)))) for a, b in zip(g1, g0)],
            "grad_scale": [float(np.max(np.abs(f32(b)))) for b in g0],
            "kernels": sorted(places)}


def cli():
    log = train_cli.main(["--arch", "h2o-danube-1.8b", "--reduced", "--steps", "2",
                          "--batch", "4", "--seq", "32", "--attn-impl", "pallas",
                          "--remat", "full", "--mesh", "2x2", "--sharding", "fsdp_tp"])
    return {"losses": [m["loss"] for m in log],
            "collectives": dict(trainer_mod.STEP_COLLECTIVES)}


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_mesh((2, 2), ("data", "model"))
    one = train(None)
    sharded = train(mesh)
    out = {"one": one, "mesh": sharded, "rollback": rollback(mesh),
           "attention": attention(mesh),
           "attention_backward": attention_backward(mesh), "cli": cli()}
    print(json.dumps(out, default=lambda x: None if not math.isfinite(x) else x))


if __name__ == "__main__":
    main()
