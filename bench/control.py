"""Readings that set the limits of a cell's comparisons, on the chip, at the
cell's size, many seeds in one process.

    python3 bench/control.py --workload qwen2-0.5b.train-2x1024 \\
        --seeds 1 2 3 --variants program fp8 half_batch

Prints one JSON line per (seed, variant) with the numbers the cell compares.

* ``program``: the program's own readings. Train cells: set up and checked
  as a run does (the window is left out, since the readings come from the
  first steps). The tune cell: the kernel's output at three tiles against
  the float32 reference.
* ``fp8`` (train cells): the float32 reference put in the program's place at
  float8, compared with the float32 reference.
* ``half_batch`` (train cells): the reference with half of each batch left
  out, the mean taken over the rest.
* ``bf16`` (the tune cell's control, the step below its float32) and ``fp8``:
  attention computed wholly in bfloat16, or on float8 operands, against the
  float32 reference on the inputs a run checks.

The benchmark's own runs never run this; PERF.md gives the readings and the
limits set from them.
"""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: tiles whose kernel output the tune cell's ``program`` variant checks: the
#: default and two that jobs choose often
PROGRAM_TILES = ({"block_q": 128, "block_kv": 128},
                 {"block_q": 512, "block_kv": 1024},
                 {"block_q": 256, "block_kv": 512})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the script's own directory, first on the path, would let the bench's
    # modules shadow others of the same name (bench/trace.py would hide the
    # standard library's trace)
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, reference

    harness.accelerators(1)
    for seed in args.seeds:
        cell = harness.resolve(args.workload, seed)
        driver = harness.load_driver(cell.traffic["driver"])
        for variant in args.variants:
            if variant == "program" and cell.traffic["driver"] == "tune":
                d = driver.Driver(cell)
                q, k, v = d._inputs()
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(lambda q, k, v: reference.attention(
                        q, k, v, dot=reference.make_dot("f32")))(q, k, v)
                errs = [driver.attention_errors(d._compiled(t)(q, k, v), ref)
                        for t in PROGRAM_TILES]
                gaps = {n: max(e[n] for e in errs) for n in errs[0]}
            elif variant == "program":
                d = driver.Driver(cell)
                d.setup()
                d.release()
                gaps = {k: v["value"] for k, v in d.check().items()}
                gaps["where"] = d.counters["check"]
            elif cell.traffic["driver"] == "train":
                numerics, fault = (("f32", variant) if variant == "half_batch"
                                   else (variant, None))
                gaps = driver.program_readings_vs(numerics, cell, fault)
            else:
                q, k, v = driver.Driver(cell)._inputs()
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(lambda q, k, v: reference.attention(
                        q, k, v, dot=reference.make_dot("f32")))(q, k, v)
                    low = jax.jit(lambda q, k, v: reference.attention(
                        q, k, v, dot=reference.make_dot(variant)))(q, k, v)
                gaps = driver.attention_errors(low.astype(ref.dtype), ref)
            print(json.dumps({"seed": seed, "variant": variant, **gaps}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
