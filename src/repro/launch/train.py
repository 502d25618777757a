"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
        --steps 200 --batch 8 --seq 128

``--reduced`` trains the tiny same-family config on the local device(s)
(the CPU-runnable path used by examples/tests); without it the full config
is used (real-hardware path), which on one TPU takes
``--attn-impl pallas --dtype bf16``.  ``--mesh 2x2 --sharding fsdp_tp``
trains on a data x model mesh over the first D*M devices, e.g. the whole
h2o-danube-1.8b on a four-chip host:

    python -m repro.launch.train --arch h2o-danube-1.8b --batch 8 \
        --seq 2048 --attn-impl pallas --dtype bf16 --remat full \
        --mesh 2x2 --sharding fsdp_tp
  The defaults (``ref`` attention, f32
compute) are the CPU path.  The fault-tolerance machinery (checkpoint /
restart / straggler detection) is active either way; ``--inject-failure``
demonstrates recovery.
"""
import argparse
import dataclasses

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.runtime import REMAT_MODES, Runtime
from repro.optim.optimizer import OptimizerConfig
from repro.runtime.fault_tolerance import FailureInjector
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--inject-failure", type=int, default=None,
                    help="simulate a worker failure at this step")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override reduced width (e.g. for the ~100M example)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--tuning-db", default=None, metavar="PATH",
                    help="persisted TuningDB (benchmarks/kernel_sweep.py "
                         "output); tuned kernel tiles are picked up at "
                         "trace time")
    ap.add_argument("--attn-impl", default="ref",
                    choices=["ref", "chunked", "pallas"],
                    help="attention implementation (kernels/ops.py)")
    ap.add_argument("--dtype", default="f32", choices=["bf16", "f32"],
                    help="compute dtype (parameters stay f32)")
    ap.add_argument("--remat", default="none", choices=REMAT_MODES,
                    help="recompute policy of the layer scan")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="train on a data x model mesh of the first D*M "
                         "devices (default: one device, no mesh)")
    ap.add_argument("--sharding", default="fsdp_tp", choices=["tp", "fsdp_tp"],
                    help="parameter sharding style on --mesh")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.d_model:
        cfg = dataclasses.replace(
            cfg, d_model=args.d_model, head_dim=args.d_model // cfg.num_heads,
            d_ff=4 * args.d_model,
        )
    if args.layers:
        period = cfg.layer_period()
        cfg = dataclasses.replace(cfg, num_layers=max(period, args.layers // period * period))

    opt_cfg = OptimizerConfig(learning_rate=args.lr, warmup_steps=20,
                              total_steps=args.steps)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    tcfg = TrainerConfig(steps=args.steps, microbatches=args.microbatches,
                         checkpoint_dir=args.checkpoint_dir,
                         checkpoint_every=args.checkpoint_every)
    injector = (FailureInjector(at_steps=[args.inject_failure])
                if args.inject_failure is not None else None)
    rt = Runtime(attn_impl=args.attn_impl, compute_dtype=args.dtype,
                 remat=args.remat)
    if args.tuning_db:
        from repro.tuning.tundb import TuningDB
        rt = dataclasses.replace(rt, tuning_db=TuningDB(args.tuning_db))
    mesh = None
    if args.mesh:
        shape = tuple(args.mesh.lower().split("x"))
        if len(shape) != 2 or not all(n.isdigit() for n in shape):
            ap.error(f"--mesh takes DxM, e.g. 2x2; got {args.mesh!r}")
        shape = tuple(int(n) for n in shape)
        mesh = make_mesh(shape, ("data", "model"))
    trainer = Trainer(cfg, opt_cfg, data_cfg, tcfg,
                      rt=rt,
                      failure_injector=injector,
                      mesh=mesh, sharding_style=args.sharding)
    log = trainer.run()
    first, last = log[0]["loss"], log[-1]["loss"]
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({len(log)} logged steps); events: {trainer.events or 'none'}")
    return log


if __name__ == "__main__":
    main()
