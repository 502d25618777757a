"""Tune the production backend for one assigned (arch x shape) cell with
the roofline objective — the paper's methodology pointed at the 256-chip
mesh (each evaluation lowers + compiles the cell).

    PYTHONPATH=src python examples/tune_backend.py \
        [--arch qwen3-moe-30b-a3b] [--shape train_4k] [--budget 12] \
        [--parallelism 4] [--wall-clock 600] \
        [--memo-cache artifacts/memo_cache.json] [--cost-aware]
        [--multi-fidelity]

How it runs (completion-driven ask/tell):

* the tuner keeps ``--parallelism`` executor workers full: the engine is
  **asked** for a candidate the moment a worker frees up, and each
  result is **told** back the moment its measurement completes — in
  completion order, so one slow compile never stalls the other workers
  at a batch barrier;
* a crashed or OOM configuration scores ``-inf`` without killing the
  worker pool, and ``--wall-clock`` budgets by seconds instead of
  iteration count — the deadline also bounds *in-flight* compiles:
  whatever is unfinished when it passes is abandoned unrecorded (a
  wall-clock budget selects a pool backend even at ``--parallelism 1``,
  since only a pool can abandon a running compile);
* every measurement is persisted twice over: the roofline compile cache
  (``--cache``, keyed by backend config) and the tuner's own
  ``--memo-cache`` (keyed by search-space point).  Both are atomic,
  file-locked JSON stores, so re-running this script re-evaluates
  nothing and concurrent runs merge rather than clobber;
* ``--parallelism 1`` (default) is the paper-faithful sequential loop,
  bit-for-bit identical to the pre-batching harness;
* multi-host: start a measurement worker per host with
  ``python examples/tune_backend.py --serve-worker --worker-port 9123``
  (same --arch/--shape so both ends agree on the objective), then drive
  the fleet with ``--backend remote --workers hostA:9123,hostB:9123`` —
  the engine, history, and memo cache stay on the tuner host, so the
  workers need no shared filesystem, and a worker dying mid-run just
  hands its in-flight compiles to the survivors.

`python -m repro.launch.tune` is the full 50-iteration driver used for
EXPERIMENTS.md §Perf; it exposes the same knobs plus --eval-timeout and
the serial/thread/process backend switch.
"""
import argparse

from repro.launch.tune import main as tune_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--budget", type=int, default=12)
    ap.add_argument("--algo", default="bo")
    ap.add_argument("--parallelism", type=int, default=1)
    ap.add_argument("--wall-clock", type=float, default=None,
                    help="seconds budget; bounds in-flight compiles too")
    ap.add_argument("--memo-cache", default="artifacts/memo_cache.json",
                    help="disk-backed memo of evaluated points; a second "
                         "run of the same job re-evaluates nothing")
    ap.add_argument("--cost-aware", action="store_true",
                    help="BO: EI-per-second acquisition (prefer cheap "
                         "compiles, sharpening as --wall-clock runs out)")
    ap.add_argument("--multi-fidelity", action="store_true",
                    help="successive-halving rungs: cheap fast-analysis "
                         "screening, top-1/eta promoted to full depth "
                         "(--budget counts full-measurement equivalents)")
    ap.add_argument("--backend", default=None,
                    choices=["serial", "thread", "process", "remote"],
                    help="evaluation backend (remote farms compiles to "
                         "--workers daemons)")
    ap.add_argument("--workers", default=None,
                    help="comma-separated host:port measurement workers "
                         "(implies --backend remote)")
    ap.add_argument("--serve-worker", action="store_true",
                    help="serve this cell's objective as a measurement "
                         "worker instead of tuning (--parallelism = "
                         "concurrent-measurement slots)")
    ap.add_argument("--worker-port", type=int, default=9123,
                    help="--serve-worker: port to listen on")
    args = ap.parse_args()
    argv = [
        "--arch", args.arch, "--shape", args.shape, "--algo", args.algo,
        "--budget", str(args.budget),
        "--parallelism", str(args.parallelism),
        "--cache", "artifacts/tune_cache.json",
        "--memo-cache", args.memo_cache,
    ]
    if args.wall_clock is not None:
        argv += ["--wall-clock", str(args.wall_clock)]
    if args.cost_aware:
        argv += ["--cost-aware"]
    if args.multi_fidelity:
        argv += ["--multi-fidelity"]
    if args.backend is not None:
        argv += ["--backend", args.backend]
    if args.workers is not None:
        argv += ["--workers", args.workers]
    if args.serve_worker:
        argv += ["--serve-worker", "--worker-port", str(args.worker_port)]
    tune_main(argv)


if __name__ == "__main__":
    main()
