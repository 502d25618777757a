"""Tuning workloads mirroring the paper's model variety (§4.1).

| paper model        | domain            | here                          |
|--------------------|-------------------|-------------------------------|
| SSD-MobileNet      | vision            | `convnet` (dw-separable CNN)  |
| ResNet50 (FP32/I8) | vision            | `convnet` precision dim       |
| Transformer-LT     | translation       | `dense_lm` (tiny qwen2)       |
| BERT               | language          | `moe_lm` (tiny qwen3-MoE)     |
| NCF                | recommendation    | `ncf` (embedding + MLP)       |
| —                  | (new) ssm         | `rwkv` (tiny RWKV-6)          |

Each workload exposes
  * ``space``      — its tunable backend parameters (paper Table 1 shape)
  * measured path  — ``make_step(point)`` for WallClockEvaluator (real
    compile+run on the local device; the paper's measurement harness)
  * surrogate path — ``surrogate_objective`` — a deterministic analytic
    throughput model (compute/memory two-term roofline + interaction and
    plateau structure + 2% hash noise) used for fast CI and the
    many-seed comparative statistics.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import numpy as np

# --- the five workload definitions -----------------------------------------

_COMMON_DIMS = [
    {"name": "batch", "type": "cat", "choices": [2, 4, 8, 16]},
    {"name": "microbatches", "type": "cat", "choices": [1, 2, 4]},
    {"name": "remat", "type": "cat", "choices": ["none", "dots", "names", "full"]},
]

MEASURED_WORKLOADS = [
    {
        "name": "dense_lm",
        "arch": "qwen2-0.5b",
        "kind": "lm",
        "space": _COMMON_DIMS + [
            {"name": "block_q", "type": "int", "min": 8, "max": 64, "step": 8},
        ],
        # surrogate shape: flops/byte weights + sweet spots
        "surr": {"flop": 1.0, "mem": 0.7, "bq_opt": 32, "mb_cost": 0.06,
                 "remat_gain": 0.25, "mode2": 0.35},
    },
    {
        "name": "moe_lm",
        "arch": "qwen3-moe-30b-a3b",
        "kind": "lm",
        "space": _COMMON_DIMS + [
            {"name": "block_q", "type": "int", "min": 8, "max": 64, "step": 8},
            {"name": "capacity_factor", "type": "cat",
             "choices": [1.0, 1.25, 1.5, 2.0]},
        ],
        "surr": {"flop": 1.1, "mem": 1.0, "bq_opt": 16, "mb_cost": 0.05,
                 "remat_gain": 0.1, "mode2": 0.55, "cf_opt": 1.25},
    },
    {
        "name": "rwkv",
        "arch": "rwkv6-3b",
        "kind": "lm",
        "space": _COMMON_DIMS + [
            {"name": "scan_chunk", "type": "int", "min": 8, "max": 64, "step": 8},
        ],
        "surr": {"flop": 0.9, "mem": 1.2, "bq_opt": 24, "mb_cost": 0.08,
                 "remat_gain": 0.35, "mode2": 0.2, "chunk_dim": "scan_chunk"},
    },
    {
        "name": "convnet",
        "arch": None,
        "kind": "conv",
        "space": _COMMON_DIMS + [
            {"name": "channels_last", "type": "cat", "choices": [0, 1]},
        ],
        "surr": {"flop": 1.3, "mem": 0.8, "bq_opt": 40, "mb_cost": 0.1,
                 "remat_gain": 0.15, "mode2": 0.45},
    },
    {
        "name": "ncf",
        "arch": None,
        "kind": "ncf",
        "space": [
            {"name": "batch", "type": "cat", "choices": [64, 128, 256, 512]},
            {"name": "microbatches", "type": "cat", "choices": [1, 2, 4]},
            {"name": "remat", "type": "cat",
             "choices": ["none", "dots", "names", "full"]},
            {"name": "embed_block", "type": "int", "min": 8, "max": 64, "step": 8},
        ],
        "surr": {"flop": 0.6, "mem": 1.5, "bq_opt": 48, "mb_cost": 0.12,
                 "remat_gain": 0.05, "mode2": 0.25, "bq_dim": "embed_block"},
    },
]


def _hash01(*vals) -> float:
    h = 0x9E3779B97F4A7C15
    for v in vals:
        h ^= abs(hash(v))
        h = (h * 0xBF58476D1CE4E5B9) % (2 ** 64)
        h ^= h >> 31
    return (h % 10_000) / 10_000.0


def surrogate_objective(workload: Dict) -> Callable[[Dict], float]:
    """Analytic two-term throughput model with the qualitative structure
    observed in the paper's Fig. 6 sweep: one dominant parameter, one
    near-flat parameter, a tile-size sweet spot, and a secondary mode."""
    s = workload["surr"]
    bq_dim = s.get("bq_dim", s.get("chunk_dim", "block_q"))

    def f(p: Dict) -> float:
        batch = p["batch"]
        mb = p["microbatches"]
        remat = p["remat"]
        bq = p.get(bq_dim, s["bq_opt"])

        # compute term: larger effective batch = better MXU utilization
        eff = batch / mb
        compute = s["flop"] / (1.0 - math.exp(-eff / 6.0))
        # tile sweet spot (primary mode) + secondary mode at half the tile
        tile = 1.0 + 0.8 * (math.log2(bq / s["bq_opt"])) ** 2 * 0.15
        tile2 = 1.0 + 0.8 * (math.log2(max(bq, 1) / max(s["bq_opt"] // 4, 1))) ** 2 * 0.15
        tile = min(tile, tile2 * (1 + s["mode2"]))
        # memory term: remat trades capacity for recompute
        remat_cost = {"none": 1.0, "dots": 1.05, "names": 1.12, "full": 1.3}[remat]
        fits = eff * (1.0 if remat != "none" else 1.6) <= 18
        mem = s["mem"] * (1.0 if fits else 4.0)  # spill cliff
        # microbatch fixed overhead
        overhead = 1.0 + s["mb_cost"] * (mb - 1)
        if "capacity_factor" in p:
            cf = p["capacity_factor"]
            overhead *= 1.0 + 0.3 * abs(cf - s.get("cf_opt", 1.25))
        step = max(compute * tile * remat_cost, mem) * overhead
        tput = 1000.0 * batch / step
        noise = 1.0 + 0.02 * (_hash01(workload["name"], tuple(sorted(p.items()))) - 0.5)
        return tput * noise

    return f


def dense_lm_objective() -> Callable[[Dict], float]:
    """Zero-argument factory for ``launch/worker.py --objective
    benchmarks.workloads:dense_lm_objective()``: the ``dense_lm``
    surrogate, built on the worker."""
    return surrogate_objective(MEASURED_WORKLOADS[0])


# --- measured (wall-clock) builders -----------------------------------------


def _lm_make_step(workload: Dict):

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.params import split_params
    from repro.models.runtime import Runtime
    from repro.optim.optimizer import OptimizerConfig, adamw_init
    from repro.train.train_step import make_train_step

    cfg = get_config(workload["arch"]).reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init(jax.random.PRNGKey(0)))
    opt_cfg = OptimizerConfig(warmup_steps=1)
    opt = adamw_init(params, opt_cfg)
    S = 64
    rng = np.random.default_rng(0)

    def make_step(point: Dict):
        B = point["batch"]
        rt = Runtime(
            compute_dtype="f32",
            remat=point["remat"],
            attn_impl="chunked",
            block_q=point.get("block_q", 32),
            block_kv=point.get("block_q", 32),
            scan_chunk=point.get("scan_chunk", 16),
            moe_capacity_factor=point.get("capacity_factor", 0.0),
        )
        step = make_train_step(model, opt_cfg, rt,
                               microbatches=point["microbatches"])
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                  jnp.int32),
            "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
        }

        def fn(params, opt, batch):
            _, _, m = step(params, opt, batch)
            return m["loss"]

        return fn, (params, opt, batch), float(B * S)

    return make_step


def _conv_make_step(workload: Dict):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    C, H = 16, 32
    ws = {
        "dw1": jnp.asarray(0.1 * rng.standard_normal((3, 3, C, 1)), jnp.float32),
        "pw1": jnp.asarray(0.1 * rng.standard_normal((1, 1, C, 2 * C)), jnp.float32),
        "dw2": jnp.asarray(0.1 * rng.standard_normal((3, 3, 2 * C, 1)), jnp.float32),
        "pw2": jnp.asarray(0.1 * rng.standard_normal((1, 1, 2 * C, 2 * C)), jnp.float32),
        "head": jnp.asarray(0.1 * rng.standard_normal((2 * C, 10)), jnp.float32),
    }

    def net(ws, x):
        for dw, pw in (("dw1", "pw1"), ("dw2", "pw2")):
            x = jax.lax.conv_general_dilated(
                x, ws[dw], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=x.shape[-1])
            x = jax.nn.relu(jax.lax.conv_general_dilated(
                x, ws[pw], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")))
        x = x.mean(axis=(1, 2))
        return x @ ws["head"]

    def make_step(point: Dict):
        B = point["batch"]
        x = jnp.asarray(rng.standard_normal((B, H, H, C)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 10, (B,)), jnp.int32)

        def loss_fn(ws):
            def inner(ws, x, y):
                logits = net(ws, x)
                return -jnp.take_along_axis(
                    jax.nn.log_softmax(logits), y[:, None], 1).mean()
            f = inner
            if point["remat"] != "none":
                f = jax.checkpoint(inner)
            if point["microbatches"] > 1:
                k = point["microbatches"]
                if B % k == 0:
                    losses = [f(ws, x[i::k], y[i::k]) for i in range(k)]
                    return sum(losses) / k
            return f(ws, x, y)

        def fn(ws):
            return jax.grad(lambda w: loss_fn(w))(ws)["head"].sum()

        return fn, (ws,), float(B)

    return make_step


def _ncf_make_step(workload: Dict):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n_users, n_items, dim = 2000, 3000, 32
    ws = {
        "ue": jnp.asarray(0.1 * rng.standard_normal((n_users, dim)), jnp.float32),
        "ie": jnp.asarray(0.1 * rng.standard_normal((n_items, dim)), jnp.float32),
        "w1": jnp.asarray(0.1 * rng.standard_normal((2 * dim, 64)), jnp.float32),
        "w2": jnp.asarray(0.1 * rng.standard_normal((64, 1)), jnp.float32),
    }

    def make_step(point: Dict):
        B = point["batch"]
        u = jnp.asarray(rng.integers(0, n_users, (B,)), jnp.int32)
        i = jnp.asarray(rng.integers(0, n_items, (B,)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, (B,)), jnp.float32)

        def loss_fn(ws):
            ue = jnp.take(ws["ue"], u, axis=0)
            ie = jnp.take(ws["ie"], i, axis=0)
            h = jax.nn.relu(jnp.concatenate([ue, ie], -1) @ ws["w1"])
            logit = (h @ ws["w2"])[:, 0] + (ue * ie).sum(-1)
            return jnp.mean(jnp.logaddexp(0.0, logit) - y * logit)

        def fn(ws):
            return jax.grad(loss_fn)(ws)["w1"].sum()

        return fn, (ws,), float(B)

    return make_step


def measured_make_step(workload: Dict):
    if workload["kind"] == "lm":
        return _lm_make_step(workload)
    if workload["kind"] == "conv":
        return _conv_make_step(workload)
    if workload["kind"] == "ncf":
        return _ncf_make_step(workload)
    raise ValueError(workload["kind"])
