"""Readings that set the limits of a mesh train cell's comparisons, on the
chips, at the cell's size, many seeds in one process: ``bench/control.py``
for cells whose driver is ``train_mesh``.

    python3 bench/control_mesh.py --workload h2o-danube-1.8b-full.train-fsdp4 \\
        --seeds 1 2 3 --variants program fp8 half_batch --deadline 500

Prints one JSON line per (seed, variant) with the numbers the cell compares
and the seconds since the start. Each seed runs the float32 split reference
once, and compares with it:

* ``program``: the program's own readings, set up as a run sets up (the
  window is left out, since the readings come from the first steps);
* ``fp8``: the split reference at float8, in the program's place;
* ``half_batch``: the split reference with half of each batch left out.

The first seed's readings come first, each variant; then the program's on
the other seeds, then the controls' on them. The reference's programs
compile on threads from the start, beside the program's set-up. With
``--deadline`` no reading starts that would end after it, by the shortest
reading of its kind so far that did not compile.

The benchmark's own runs never run this; PERF.md gives the readings and the
limits set from them.
"""
import argparse
import json
import os
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: (numerics, fault) of the reference put in the program's place
CONTROLS = {"fp8": ("fp8", None), "half_batch": ("f32", "half_batch")}


def main(argv=None, root: pathlib.Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", required=True,
                    choices=("program", *CONTROLS))
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="seconds from the start after which no reading ends")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    from bench import harness, reference_sharded

    cells = [harness.resolve(args.workload, s, root) for s in args.seeds]
    driver = harness.load_driver(cells[0].traffic["driver"], root / "bench")
    t, m = cells[0].traffic, cells[0].config
    devices = harness.accelerators(cells[0].chips)
    controls = [v for v in args.variants if v in CONTROLS]
    pool = ThreadPoolExecutor(1 + len(controls))
    for numerics, fault in [("f32", None)] + [CONTROLS[v] for v in controls]:
        pool.submit(reference_sharded.programs, m, t["optimizer"],
                    (t["batch"], t["seq_len"]), devices, numerics=numerics, fault=fault)

    took, refs = {}, {}

    def reading(kind, seed, fn):
        """``fn()``'s gaps, printed, unless the deadline would pass first."""
        start = time.perf_counter()
        warm = took.get(kind, [])[1:]  # the first of a kind compiles
        if start - t0 + min(warm, default=0.0) > args.deadline:
            print(json.dumps({"seed": seed, "variant": kind, "skipped": "deadline",
                              "seconds": start - t0}), flush=True)
            return
        gaps = fn()
        took.setdefault(kind, []).append(time.perf_counter() - start)
        print(json.dumps({"seed": seed, "variant": kind, **gaps,
                          "seconds": time.perf_counter() - t0}), flush=True)

    def program(cell):
        """The program's gaps; the seed's reference, kept for the controls."""
        readings = None
        if "program" in args.variants:
            d = driver.Driver(cell)
            d.setup()
            d.release()
            readings = d.readings
        refs[cell.seed] = reference_sharded.lm_readings(
            m, t["optimizer"], driver._batches(cell), cell.seed, devices)
        if readings is None:
            return {}
        gaps = driver._train.compare(readings, refs[cell.seed])
        return {"where": gaps.pop("_where"), **gaps}

    def control(cell, variant):
        if cell.seed not in refs:
            return {"skipped": "no reference"}
        numerics, fault = CONTROLS[variant]
        return driver.program_readings_vs(numerics, cell, fault, ref=refs[cell.seed])

    kind = "program" if "program" in args.variants else "reference"
    runs = list(zip(args.seeds, cells))
    (seed, first), rest = runs[0], runs[1:]
    reading(kind, seed, lambda: program(first))
    for v in controls:
        reading(v, seed, lambda: control(first, v))
    for seed, cell in rest:
        reading(kind, seed, lambda: program(cell))
    for seed, cell in rest:
        for v in controls:
            reading(v, seed, lambda: control(cell, v))
    pool.shutdown()
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # as in bench/control.py: the bench's modules must not shadow others
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
