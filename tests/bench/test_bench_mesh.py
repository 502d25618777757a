"""The four-chip cell: its share of the benchmark's cells, its readers, and,
on four virtual CPU devices in one process (``mesh_bench_checks.py``), the
split reference against the one-device reference, its controls and a whole
run of the cell at a tiny size."""
import json
import math
import os
import subprocess
import sys
import types

import pytest

from bench import harness
from bench_fixtures import REPO

DOC = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "h2o-danube-1.8b-full.train-fsdp4"


def test_four_chip_cells_are_at_most_half_of_the_cells():
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 2)


# -- readers -------------------------------------------------------------------


def test_collective_bytes_reads_the_counter(monkeypatch):
    from repro.train import trainer

    read = harness.load_reader("train_collective_bytes")
    monkeypatch.setattr(trainer, "STEP_COLLECTIVES",
                        {"all-gather": 3_000_000_000, "reduce-scatter": 1_500_000_000})
    assert read({}) == pytest.approx(4.5)
    monkeypatch.setattr(trainer, "STEP_COLLECTIVES", {})
    assert read({}) is None
    monkeypatch.delattr(trainer, "STEP_COLLECTIVES")
    assert read({}) is None


def _table(rows):
    """An ``hlo_stats`` table as ``xprof`` returns it, with the columns the
    reader reads."""
    cols = ["rank", "category", "hlo_op_name", "hlo_op_expression",
            "tf_op_name", "total_self_time"]
    return {"cols": [{"id": c} for c in cols],
            "rows": [{"c": [{"v": v} for v in (i, *r)]} for i, r in enumerate(rows)]}


#: (category, name, text, framework op, self time in microseconds) summed
#: over two chips, in the forms a TPU v5e trace of the fsdp_tp step shows;
#: the collectives are 300 + 40 + 60 + 20 + 10 = 430
STATS = [
    ("convolution fusion", "fusion.1", "%fusion.1 = bf16[8] fusion(%all-gather.2)",
     "jit(step)/dot_general", 900.0),
    ("all-gather", "all-gather.3", "%all-gather.3 = bf16[8] all-gather(%p)",
     "jit(step)/all_gather", 300.0),
    ("convolution fusion", "fusion.4", "%fusion.4 = (bf16[8], bf16[16]) fusion(%p), "
     "kind=kOutput, calls=%async_collective_fusion.4", "jit(step)/dot_general", 100.0),
    ("all-reduce-scatter fusion", "fusion.5", "%fusion.5 = bf16[4] fusion(%g), "
     "kind=kCustom, calls=%all-reduce-scatter.5", "jit(step)/transpose", 40.0),
    ("all-reduce", "all-reduce.6", "%all-reduce.6 = bf16[8] all-reduce(%s)",
     "jit(step)/psum", 60.0),
    ("custom fusion", "async-collective-start.8", "%async-collective-start.8 = "
     "(bf16[4], bf16[8]) fusion(%w), kind=kCustom", "jit(step)/dot_general", 20.0),
    ("custom fusion", "async-collective-done.9", "%async-collective-done.9 = "
     "bf16[8] fusion(%w), kind=kCustom", "jit(step)/dot_general", 10.0),
    ("loop fusion", "add.7", "%add.7 = f32[8] add(%a, %b)", "jit(step)/add", 50.0),
]


def test_collective_share_reads_collective_self_time(monkeypatch, tmp_path):
    read = harness.load_reader("train_collective_share")
    seconds = read.__globals__["collective_seconds"]
    assert seconds(_table(STATS), 2) == pytest.approx(215e-6)
    assert seconds(_table(STATS[-1:]), 2) is None

    from xprof.convert import raw_to_tool_data

    monkeypatch.setattr(raw_to_tool_data, "xspace_to_tool_data",
                        lambda paths, tool, opts: (json.dumps(_table(STATS)), None))
    run_dir = tmp_path / "run"
    (run_dir / "trace" / "plugins" / "profile" / "t").mkdir(parents=True)
    (run_dir / "trace" / "plugins" / "profile" / "t" / "h.xplane.pb").write_bytes(b"")
    ctx = {"cell": types.SimpleNamespace(run_dir=run_dir), "n_chips": 2,
           "trace": {"busy_s": 0.001, "window_s": 0.0025}}
    assert read(ctx) == pytest.approx(8.6)
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, cell=types.SimpleNamespace(run_dir=tmp_path / "none"))) is None


# -- four virtual devices ------------------------------------------------------


def _one_core():
    os.nice(10)
    os.sched_setaffinity(0, {max(os.sched_getaffinity(0))})


@pytest.fixture(scope="module")
def four():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO), here]))
    # one core at a lower priority: no more load than the worker that waits
    # for it, so the parallel run's timed windows (the tune driver's tests)
    # keep their share of the CPU
    out = subprocess.run([sys.executable, os.path.join(here, "mesh_bench_checks.py")],
                         env=env, capture_output=True, text=True, timeout=900,
                         preexec_fn=_one_core)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_split_reference_matches_the_one_device_reference(four):
    one, split = four["references"]["one"], four["references"]["four"]
    # the same float32 mathematics; the split only reorders sums
    assert split["loss"] == pytest.approx(one["loss"], rel=1e-5)
    for part in ("grad", "change"):
        assert set(split[part]) == set(one[part])
        for name, v in one[part].items():
            assert split[part][name] == pytest.approx(v, rel=1e-3, abs=1e-7), (part, name)


@pytest.mark.parametrize("variant", ["fp8", "half_batch"])
def test_mesh_controls_fail_under_the_cells_limits(four, variant):
    limits = json.loads((REPO / "bench" / "limits" / f"{CELL}.json").read_text())
    gaps = four["controls"][variant]
    assert any(gaps[k] > limits[k] for k in limits), gaps


def test_mesh_fp8_control_is_not_correct(four):
    """The control through a whole run: the split reference at float8 in
    the program's place, judged by the harness under the cell's limits."""
    limits = json.loads((REPO / "bench" / "limits" / f"{CELL}.json").read_text())
    r = four["fp8_run"]
    assert {k: c["limit"] for k, c in r["compared"].items()} == limits
    assert r["correct"] is False, r["compared"]


def test_control_mesh_reads_the_first_seed_first(four):
    lines = four["control"]
    seeds = sorted({line["seed"] for line in lines})
    order = [(line["seed"] - seeds[0], line["variant"]) for line in lines]
    assert order == [(0, "program"), (0, "fp8"), (0, "half_batch"),
                     (1, "program"), (2, "program"),
                     (1, "fp8"), (1, "half_batch"), (2, "fp8"), (2, "half_batch")]
    for line in lines:
        assert {"loss_gap", "grad_gap", "change_gap"} <= set(line), line
        assert all(math.isfinite(line[k]) for k in ("loss_gap", "grad_gap", "change_gap"))
    # the readings leave the seconds since the start in order
    assert [line["seconds"] for line in lines] == sorted(line["seconds"] for line in lines)
    # past the deadline no reading starts
    assert [line.get("skipped") for line in four["control_deadline"]] == ["deadline"] * 2


def test_mesh_cell_runs_correct(four):
    r = four["run"]
    assert r["correct"] is True, r["compared"]
    assert r["device"]["count"] == 4
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(math.isfinite(m["value"]) for m in r["metrics"].values())
