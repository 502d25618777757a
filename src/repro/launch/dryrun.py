import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^^^ MUST precede any jax import: jax locks the device count on first init.
#     (setdefault so test harnesses can inject a smaller placeholder count.)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces
  * ``memory_analysis()``        — proves the step fits per-device HBM
  * ``cost_analysis()``          — per-device HLO FLOPs / bytes
  * collective-bytes breakdown   — parsed from the SPMD HLO text, while-body
                                   ops scaled by known_trip_count
  * the three-term roofline      — tuning/cost_model.py

HloCostAnalysis counts scan (while) bodies ONCE, so FLOPs/bytes come from
two extra *unrolled* compiles at 1 and 2 layer-periods, extrapolated
linearly to the full depth (exact: the out-of-loop part cancels).

CLI:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all --out artifacts/dryrun
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Dict

import numpy as np

from repro.configs import SHAPES, applicable, get_config, get_shape, list_archs
from repro.configs.base import ModelConfig
from repro.launch.cells import lower_cell
from repro.launch.mesh import make_mesh
from repro.tuning.cost_model import (
    Roofline,
    analytic_hbm_traffic,
    kernel_traffic_bytes,
    model_flops,
    tokens_per_step,
    weighted_collective_bytes,
)
from repro.tuning.hlo_analysis import (
    collect_collective_stats,
    cost_with_scan_correction,
    traffic_analysis,
)
from repro.tuning.parameters import BASELINE, BackendConfig


def build_cell_mesh(bc: BackendConfig, *, multi_pod: bool, chips_per_pod: int = 256):
    dp, tp = bc.dp(chips_per_pod), bc.tp(chips_per_pod)
    if multi_pod:
        return make_mesh((2, dp, tp), ("pod", "data", "model"))
    return make_mesh((dp, tp), ("data", "model"))


def _reduced_depth_cfg(cfg: ModelConfig, n_periods: int) -> ModelConfig:
    period = cfg.layer_period()
    kw = {"num_layers": n_periods * period}
    if cfg.encoder_layers:
        kw["encoder_layers"] = n_periods
    return dataclasses.replace(cfg, **kw)


def _compile_costs(cfg, shape, mesh, bc) -> Dict[str, float]:
    lowered = lower_cell(cfg, shape, mesh, bc)
    compiled = lowered.compile()
    out = cost_with_scan_correction(compiled)
    tr = traffic_analysis(compiled.as_text())
    out["traffic_included"] = tr.included_bytes
    out["traffic_excluded"] = tr.excluded_bytes
    return out


def analyze_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    bc: BackendConfig = BASELINE,
    chips_per_pod: int = 256,
    full_text: bool = False,
    fast: bool = False,
) -> Dict:
    """Full dry-run + roofline for one cell."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": True, "skip_reason": reason}

    mesh = build_cell_mesh(bc, multi_pod=multi_pod, chips_per_pod=chips_per_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    # 1) full-depth scan compile: memory + collectives (trip-scaled)
    lowered = lower_cell(cfg, shape, mesh, bc)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collect_collective_stats(hlo)
    full_cost = cost_with_scan_correction(compiled)
    t_full = time.time() - t0

    # 2) unrolled 1- and 2-period compiles -> exact flops/bytes extrapolation.
    # block_q is floored for the cost compiles so prefill-32k doesn't unroll
    # 64 chunk bodies (FLOPs are tile-size independent modulo pruning
    # granularity); skipped entirely in fast mode (multi-pod pass, whose
    # deliverable is shard/compile/memory proof — roofline is single-pod).
    n_periods = cfg.num_layers // cfg.layer_period()
    cost_bq = max(bc.block_q, shape.seq_len // 8) if shape.kind != "decode" else bc.block_q
    bc_unroll = bc.replace(unroll_layers=True, block_q=cost_bq)
    # long-period MoE-hybrid bodies (jamba: 8 layers incl. 16-expert MoE)
    # make the unrolled cost compiles pathologically slow on this 1-core
    # host; fall back to trip-count scaling for them (documented few-%%
    # overcount of the out-of-loop part).
    fast = fast or cfg.layer_period() >= 8
    if fast or n_periods == 1:
        tr = traffic_analysis(hlo)
        flops_pd = full_cost["flops"]
        bytes_raw = full_cost["bytes"]
        traffic_in = tr.included_bytes
        traffic_ex = tr.excluded_bytes
        if fast and n_periods > 1:
            # scan bodies counted once: scale by trip count as a first-order
            # correction (exact extrapolation lives in the single-pod pass)
            flops_pd *= n_periods
            bytes_raw *= n_periods
    else:
        c1 = _compile_costs(_reduced_depth_cfg(cfg, 1), shape, mesh, bc_unroll)
        c2 = _compile_costs(_reduced_depth_cfg(cfg, 2), shape, mesh, bc_unroll)
        ex = lambda k: c1[k] + (n_periods - 1) * (c2[k] - c1[k])
        flops_pd = ex("flops")
        bytes_raw = ex("bytes")
        traffic_in = ex("traffic_included")
        traffic_ex = ex("traffic_excluded")
    # Memory term (DESIGN.md §7): three estimates, most->least pessimistic:
    #   bytes_hlo_raw    — cost_analysis on the CPU-lowered HLO (spec formula;
    #                      counts the unfused softmax/scan chains)
    #   traffic_in + kernel credit — per-op traffic with the Pallas-kernel
    #                      regions credited at their true stream traffic
    #   analytic         — TPU-grade-fusion model (headline term)
    kernel_credit = kernel_traffic_bytes(cfg, shape, bc, chips)
    traffic_adjusted = max(traffic_in, 0.0) + kernel_credit
    analytic = analytic_hbm_traffic(cfg, shape, bc, chips)
    bytes_adjusted = analytic["total"]

    mem_per_device = (
        mem.argument_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
    )
    n_active = cfg.param_counts()["active"]
    rf = Roofline(
        flops_per_device=flops_pd,
        bytes_per_device=bytes_adjusted,
        collective_bytes=weighted_collective_bytes(coll.bytes_by_kind),
        tokens_per_step=tokens_per_step(shape),
        chips=chips,
        model_flops=model_flops(cfg, shape, n_active),
        memory_per_device=float(mem_per_device),
        collective_detail=coll.summary(),
        bytes_hlo_raw=bytes_raw,
        bytes_kernel_credit=kernel_credit,
    )
    rec = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "skipped": False,
        "chips": chips,
        "mesh": dict(mesh.shape),
        "backend": dataclasses.asdict(bc),
        "memory": {
            "argument_B": mem.argument_size_in_bytes,
            "temp_B": mem.temp_size_in_bytes,
            "output_B": mem.output_size_in_bytes,
            "alias_B": mem.alias_size_in_bytes,
            "per_device_B": float(mem_per_device),
        },
        "cost": {
            "flops_per_device": flops_pd,
            "bytes_hlo_raw": bytes_raw,
            "bytes_traffic_included": traffic_in,
            "bytes_traffic_kernel_excluded": traffic_ex,
            "bytes_kernel_credit": kernel_credit,
            "bytes_traffic_adjusted": traffic_adjusted,
            "bytes_analytic": analytic,
            "bytes_adjusted": bytes_adjusted,
            "scan_body_flops_once": full_cost["flops"],
            "n_periods": n_periods,
        },
        "collectives": {
            "bytes_by_kind": dict(coll.bytes_by_kind),
            "count_by_kind": dict(coll.count_by_kind),
            "weighted_bytes": weighted_collective_bytes(coll.bytes_by_kind),
        },
        "roofline": rf.row(),
        "params": cfg.param_counts(),
        "compile_seconds": t_full,
    }
    if full_text:
        rec["hlo"] = hlo
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all (arch x shape) cells")
    ap.add_argument("--out", default=None, help="JSON output path or dir")
    ap.add_argument("--chips-per-pod", type=int, default=256)
    ap.add_argument("--log2-dp", type=int, default=BASELINE.log2_dp)
    ap.add_argument("--style", default=BASELINE.sharding_style)
    ap.add_argument("--remat", default=BASELINE.remat)
    ap.add_argument("--microbatches", type=int, default=BASELINE.microbatches)
    args = ap.parse_args(argv)

    bc = BASELINE.replace(
        log2_dp=args.log2_dp, sharding_style=args.style, remat=args.remat,
        microbatches=args.microbatches,
    )
    results = []

    cells = []
    if args.all:
        for arch in list_archs():
            for shape_name in SHAPES:
                cells.append((arch, shape_name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    done = set()
    if args.out:
        import pathlib

        jl = pathlib.Path(str(args.out) + ".jsonl")
        if jl.exists():  # restart-safe: skip cells already recorded
            for line in jl.read_text().splitlines():
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], bool(r.get("multi_pod"))))
                        results.append(r)
                except Exception:
                    pass

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for arch, shape_name in cells:
        for mp in meshes:
            if (arch, shape_name, mp) in done:
                continue
            tag = f"{arch}/{shape_name}/{'multi' if mp else 'single'}"
            try:
                rec = analyze_cell(arch, shape_name, multi_pod=mp, bc=bc,
                                   chips_per_pod=args.chips_per_pod,
                                   fast=mp)
                results.append(rec)
                if rec.get("skipped"):
                    print(f"[dryrun] {tag}: SKIP ({rec['skip_reason']})")
                else:
                    r = rec["roofline"]
                    print(
                        f"[dryrun] {tag}: OK mem/dev "
                        f"{rec['memory']['per_device_B']/1e9:.2f}GB "
                        f"bottleneck={r['bottleneck']} "
                        f"step={r['est_step_s']*1e3:.2f}ms "
                        f"tput={r['throughput_tok_s']:.3g}tok/s "
                        f"compile={rec['compile_seconds']:.0f}s"
                    )
            except Exception as e:  # report, keep going
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape_name,
                                "multi_pod": mp, "error": str(e)})
                print(f"[dryrun] {tag}: FAIL {e}")
            if args.out:  # incremental (restart-safe) record
                import pathlib

                pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
                with open(str(args.out) + ".jsonl", "a") as f:
                    f.write(json.dumps(results[-1], default=str) + "\n")
            sys.stdout.flush()

    if args.out:
        import pathlib

        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(results, indent=1, default=str))
        print(f"[dryrun] wrote {out}")
    return results


if __name__ == "__main__":
    main()
