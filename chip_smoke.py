"""Smoke run of the tuner's main path on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip: kernels -> tune -> train -> serve
    python chip_smoke.py --chips 4   # four chips: sharded vs one-chip train step

One chip, in order, each phase raising on any failure:

* kernels: each of the five Pallas kernels runs once, compiled by Mosaic
  (its HLO holds a ``tpu_custom_call``), at qwen2-0.5b widths (the scans
  at jamba / rwkv6-3b widths), against the float32 oracles of
  ``repro.kernels.ref`` at bf16 tolerance;
* tune: ``benchmarks/kernel_sweep.run_sweep`` tunes flash and decode
  attention at the shapes the train and serve phases call them with, into
  a TuningDB under ``artifacts/chip_smoke/`` emptied first;
* train: ``launch/train.main`` on full-width qwen2-0.5b with Pallas
  attention, bf16 compute and that TuningDB; finite losses, and the step-0
  loss agrees with a ``chunked``-attention run on the same batch;
* serve: ``launch/serve.main`` at full width with Pallas attention and the
  TuningDB; the prefill's last-token log-probs agree with the ``ref`` path.

``--chips 4`` runs only the sharded train step that ``launch/cells.py``
builds (``fsdp_tp`` over a 2x2 mesh) and, on the same batches, the same
step on one chip; their losses must agree.

Everything runs in this one process, which owns the chip(s): no child
process touches JAX.  Without a TPU it exits non-zero before any work.
Times and memory printed on the way are smoke numbers, not benchmark
metrics.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

ARCH = "qwen2-0.5b"
# 2 x 1024 tokens per step: at 4 x 1024 the v5e compiler refuses the
# Pallas-forward/reference-backward step (16.05 GB of 15.75 GB HBM)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 1024, 3
SERVE_REQUESTS, PROMPT_LEN, GEN_LEN = 8, 512, 32
# float32 oracle vs a bf16 result; loss and log-prob bounds are for two
# bf16 runs that differ only in their attention implementation
KERNEL_TOL = 2e-2
LOSS_RTOL = 1e-2
LOGPROB_ATOL = 1e-1
DB_DIR = ROOT / "artifacts" / "chip_smoke"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int):
    """The devices, or exit non-zero before any work when they are not
    ``count`` or more TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devices)}")
    return devices


def peak_hbm_gb() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 1e9


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _kernel_cases():
    """(name, pallas fn, f32 oracle fn, args) at the model widths."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    q_cfg = get_config(ARCH)
    H, K, dh = q_cfg.num_heads, q_cfg.num_kv_heads, q_cfg.resolved_head_dim
    f32 = lambda *a: [x.astype(jnp.float32) for x in a]

    attn = (normal((TRAIN_BATCH, TRAIN_SEQ, H, dh)),
            normal((TRAIN_BATCH, TRAIN_SEQ, K, dh)),
            normal((TRAIN_BATCH, TRAIN_SEQ, K, dh)))
    cache_len = PROMPT_LEN + GEN_LEN
    dec = (normal((SERVE_REQUESTS, H, dh)),
           normal((SERVE_REQUESTS, cache_len, K, dh)),
           normal((SERVE_REQUESTS, cache_len, K, dh)),
           jax.random.randint(next(keys), (SERVE_REQUESTS,), 1, cache_len + 1))
    norm = (normal((TRAIN_BATCH * TRAIN_SEQ, q_cfg.d_model)),
            normal((q_cfg.d_model,), jnp.float32, 0.1) + 1.0)

    jamba = get_config("jamba-v0.1-52b")
    D, N = jamba.mamba.expand * jamba.d_model, jamba.mamba.d_state
    S = 512
    ssm = (normal((1, S, D)),
           jax.nn.softplus(normal((1, S, D), jnp.float32) - 2.0).astype(jnp.bfloat16),
           -jnp.exp(normal((D, N), jnp.float32, 0.5)),
           normal((1, S, N)), normal((1, S, N)),
           normal((D,), jnp.float32))

    rwkv = get_config("rwkv6-3b")
    Hr, hs = rwkv.d_model // rwkv.rwkv.head_size, rwkv.rwkv.head_size
    gla = (normal((1, S, Hr, hs)), normal((1, S, Hr, hs)), normal((1, S, Hr, hs)),
           jnp.exp(-jnp.exp(normal((1, S, Hr, hs), jnp.float32, 0.5) - 1.0)
                   ).astype(jnp.bfloat16),
           normal((Hr, hs), jnp.float32))

    return [
        ("flash_attention",
         lambda q, k, v: ops.attention(q, k, v, impl="pallas"),
         lambda q, k, v: ref.attention_ref(*f32(q, k, v)), attn),
        ("decode_attention",
         lambda q, k, v, n: ops.decode_attention(q, k, v, n, impl="pallas"),
         lambda q, k, v, n: ref.decode_attention_ref(*f32(q, k, v), n), dec),
        ("rmsnorm",
         lambda x, s: ops.rmsnorm(x, s, impl="pallas"),
         lambda x, s: ref.rmsnorm_ref(*f32(x, s)), norm),
        ("ssm_scan",
         lambda *a: ops.ssm_scan(*a, impl="pallas"),
         lambda *a: ref.ssm_scan_ref(*f32(*a))[0], ssm),
        ("gla_scan",
         lambda *a: ops.gla_scan(*a, impl="pallas"),
         lambda *a: ref.gla_scan_ref(*f32(*a))[0], gla),
    ]


def phase_kernels() -> None:
    import jax
    import numpy as np

    for name, fn, oracle, args in _kernel_cases():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the compiled "
                                 "HLO (the kernel did not go through Mosaic)")
        out = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):  # a true f32 oracle
            want = np.asarray(jax.jit(oracle)(*args), np.float32)
        if not np.isfinite(out).all():
            raise AssertionError(f"{name}: non-finite output")
        err = float(np.max(np.abs(out - want)))
        np.testing.assert_allclose(out, want, atol=KERNEL_TOL, rtol=KERNEL_TOL,
                                   err_msg=f"{name} vs its f32 oracle")
        log(f"kernel {name}: shape {tuple(out.shape)}, compile "
            f"{compile_s:.3f} s, max |err| {err:.3g} (tol {KERNEL_TOL})")


# ---------------------------------------------------------------------------
# tune
# ---------------------------------------------------------------------------


def tune_shapes():
    """flash_attention as the train step calls it, decode_attention as the
    serve phase's decode step calls it."""
    from repro.configs import get_config

    cfg = get_config(ARCH)
    H, K, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "flash_attention": {"B": TRAIN_BATCH, "Sq": TRAIN_SEQ, "Sk": TRAIN_SEQ,
                            "H": H, "K": K, "dh": dh},
        "decode_attention": {"B": SERVE_REQUESTS, "H": H, "K": K, "dh": dh,
                             "Smax": PROMPT_LEN + GEN_LEN},
    }


def phase_tune(db_path: pathlib.Path) -> None:
    from benchmarks.kernel_sweep import run_sweep
    from repro.tuning.tundb import TuningDB

    shutil.rmtree(db_path.parent, ignore_errors=True)
    db_path.parent.mkdir(parents=True)
    shapes = tune_shapes()
    t0 = time.perf_counter()
    rows, measured = run_sweep(sorted(shapes), TuningDB(db_path), budget=3,
                               shapes=shapes, emit=log)
    for row in rows:
        if row["skipped"] or not math.isfinite(row["value"]):
            raise AssertionError(f"tune {row['kernel']}: no finite "
                                 f"measurement ({row})")
    fresh = TuningDB(db_path)
    for kernel, shape in shapes.items():
        log(f"tune {kernel}: trace-time lookup -> "
            f"{fresh.kernel_config(kernel, shape)}")
    log(f"tune: {measured} measurements in {time.perf_counter() - t0:.3f} s "
        f"(compiles included)")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def phase_train(db_path: pathlib.Path) -> None:
    from repro.launch import train

    argv = ["--arch", ARCH, "--batch", str(TRAIN_BATCH), "--seq",
            str(TRAIN_SEQ), "--dtype", "bf16", "--tuning-db", str(db_path)]
    t0 = time.perf_counter()
    metrics = train.main(argv + ["--steps", str(TRAIN_STEPS),
                                 "--attn-impl", "pallas"])
    seconds = time.perf_counter() - t0
    losses = [m["loss"] for m in metrics]
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"train: losses {losses}")
    log(f"train pallas: losses {losses}; {TRAIN_STEPS} steps of "
        f"{TRAIN_BATCH} x {TRAIN_SEQ} tokens in {seconds:.3f} s (init and "
        f"compile included); peak HBM {peak_hbm_gb():.3f} GB")
    gc.collect()
    ref_loss = train.main(argv + ["--steps", "1", "--attn-impl", "chunked"]
                          )[0]["loss"]
    log(f"train step-0 loss: pallas {losses[0]!r}, chunked {ref_loss!r}")
    if not math.isclose(losses[0], ref_loss, rel_tol=LOSS_RTOL):
        raise AssertionError(f"train: step-0 loss {losses[0]} vs chunked "
                             f"{ref_loss} (rel tol {LOSS_RTOL})")
    gc.collect()


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def phase_serve(db_path: pathlib.Path) -> None:
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve

    argv = ["--arch", ARCH, "--requests", str(SERVE_REQUESTS), "--batch",
            str(SERVE_REQUESTS), "--prompt-len", str(PROMPT_LEN), "--dtype",
            "bf16", "--tuning-db", str(db_path)]
    got = serve.main(argv + ["--gen-len", str(GEN_LEN), "--attn-impl",
                             "pallas"])
    vocab = get_config(ARCH).padded_vocab
    for rid, toks in got["outputs"]:
        if toks.shape != (GEN_LEN,) or not ((0 <= toks) & (toks < vocab)).all():
            raise AssertionError(f"serve: request {rid} produced {toks}")
    log(f"serve pallas: {len(got['outputs'])} requests x {GEN_LEN} tokens in "
        f"{got['seconds']:.3f} s (compiles included); peak HBM "
        f"{peak_hbm_gb():.3f} GB")
    gc.collect()
    want = serve.main(argv + ["--gen-len", "1", "--attn-impl", "ref"])
    lp, lp_ref = got["prefill_logprobs"], want["prefill_logprobs"]
    if not np.isfinite(lp).all():
        raise AssertionError("serve: non-finite prefill log-probs")
    err = float(np.max(np.abs(lp - lp_ref)))
    log(f"serve prefill last-token log-probs: max |pallas - ref| {err:.4g} "
        f"(tol {LOGPROB_ATOL})")
    if err > LOGPROB_ATOL:
        raise AssertionError(f"serve: prefill log-probs differ from ref by "
                             f"{err} > {LOGPROB_ATOL}")
    gc.collect()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def sharded_vs_one_chip(cfg, seq: int, batch: int, steps: int):
    """Train ``steps`` steps of the cell step on a (2, 2) ``fsdp_tp`` mesh,
    then the same step on one device from the same initial weights and
    batches; raise unless every step's loss agrees within ``LOSS_RTOL``.
    Returns the two loss lists."""
    import jax

    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, SyntheticTokens
    from repro.launch.cells import build_cell_step
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model
    from repro.models.params import split_params
    from repro.optim.optimizer import adamw_init
    from repro.tuning.parameters import BASELINE

    shape = ShapeConfig("chip_smoke_train", seq, batch, "train")
    bc = BASELINE.replace(log2_dp=1, sharding_style="fsdp_tp")
    data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                      global_batch=batch))
    model = build_model(cfg)
    losses = {}
    for name, mesh_shape in (("sharded 2x2", (2, 2)), ("one chip", (1, 1))):
        cell = build_cell_step(cfg, shape, make_mesh(mesh_shape,
                                                     ("data", "model")), bc)
        params_sh, opt_sh, batch_sh = cell.shardings
        params = jax.jit(lambda: split_params(model.init(jax.random.PRNGKey(0)))[0],
                         out_shardings=params_sh)()
        opt = jax.jit(lambda p: adamw_init(p, cell.opt_cfg),
                      out_shardings=opt_sh)(params)
        losses[name] = []
        t0 = time.perf_counter()
        for step in range(steps):
            batch_np = data.batch_at(step)
            feed = {k: jax.device_put(batch_np[k], batch_sh[k]) for k in batch_sh}
            params, opt, metrics = cell.jitted(params, opt, feed)
            losses[name].append(float(metrics["loss"]))
        log(f"{name}: losses {losses[name]} in {time.perf_counter() - t0:.3f} s "
            f"(compile included)")
        del params, opt
        gc.collect()
    sharded, single = losses.values()
    for step, (a, b) in enumerate(zip(sharded, single)):
        if not (math.isfinite(a) and math.isclose(a, b, rel_tol=LOSS_RTOL)):
            raise AssertionError(f"step {step}: sharded loss {a} vs one-chip "
                                 f"{b} (rel tol {LOSS_RTOL})")
    return sharded, single


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the one-chip phases; 4: only the sharded "
                         "train step against the one-chip step")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; the numbers "
        "below are smoke numbers, not benchmark metrics")
    if args.chips == 4:
        from repro.configs import get_config

        sharded_vs_one_chip(get_config(ARCH), TRAIN_SEQ, 4, TRAIN_STEPS)
    else:
        db_path = DB_DIR / "tundb.json"
        for name, phase in (("kernels", phase_kernels),
                            ("tune", lambda: phase_tune(db_path)),
                            ("train", lambda: phase_train(db_path)),
                            ("serve", lambda: phase_serve(db_path))):
            t0 = time.perf_counter()
            phase()
            log(f"phase {name} passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
