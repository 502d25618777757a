"""Run one cell of the benchmark once.

    python3 bench/run.py --workload qwen2-0.5b.train-2x1024 --seed 7 \\
        --seconds 30 --trace 0

Prints the result as the last line of standard output: a JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number the
correctness check compared, beside its limit. The same numbers end standard
error. Without an accelerator, or without the program beside the benchmark,
it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    # the compile cache sits at a fixed path inside the checkout, whatever
    # the machine sets: runs of one checkout share it, two checkouts never
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the script's own directory, first on the path, would let the bench's
    # modules shadow others of the same name (bench/trace.py would hide the
    # standard library's trace)
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    compared = " ".join(f"{k}={v['value']!r}<={v['limit']!r}"
                        for k, v in result["compared"].items())
    print(f"compared: {compared}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
