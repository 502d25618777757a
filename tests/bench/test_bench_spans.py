"""The readers of what the program names in a run: idle time under host
spans, device time under kernel scopes, and the trials' phase counters;
on events built by hand, on traces recorded on a TPU v5e, and on the
CPU."""
import pathlib
import shutil
import types

import pytest

from bench import harness, spans, trace

DATA = pathlib.Path(__file__).parent / "data"
#: a tiny train step recorded on a TPU v5e with the loop's spans and the
#: kernels' scopes; ``small_trace`` predates both
STEP_TRACE = DATA / "train_step.xplane.pb"
OLD_TRACE = DATA / "small_trace.xplane.pb"
TRAIN_READERS = ("train_feed_share", "train_sync_share", "train_attn_share")
TUNE_READERS = ("tune_lower_share", "tune_compile_share")


def _planes():
    return [
        {"name": "/device:TPU:0", "lines": {
            "XLA Ops": [("fusion", 10.0, 20.0), ("dot", 60.0, 20.0)]}},
        {"name": "/device:TPU:1", "lines": {
            "XLA Ops": [("fusion", 10.0, 40.0), ("dot", 60.0, 20.0)]}},
        {"name": "/host:CPU", "lines": {
            "python3": [("run", 0.0, 100.0), ("train.feed", 20.0, 30.0),
                        ("asarray", 40.0, 10.0)],
            "other": [("train.sync", 85.0, 10.0)]}},
    ]


def test_idle_under_counts_spans_at_any_depth_and_on_any_thread():
    # TPU:0 is idle over [0,10], [30,60], [80,100]; the feed covers [30,50]
    # of it though ``asarray`` is the innermost event over [40,50]; the
    # sync runs on another thread; [0,10] and [50,60] lie under no span
    got = spans.idle_under(_planes(), ["train.feed", "train.sync", "absent"], 1)
    assert got == {"train.feed": pytest.approx(20e-9),
                   "train.sync": pytest.approx(10e-9)}
    assert trace.attribute([(30.0, 60.0)], _planes()[2]["lines"]["python3"]) == {
        "train.feed": 10.0, "asarray": 10.0, "run": 10.0}


def test_idle_under_is_a_mean_over_the_chips():
    # TPU:1 is busy until 50, so the feed keeps no idle time there
    got = spans.idle_under(_planes(), ["train.feed"], 2)
    assert got == {"train.feed": pytest.approx(10e-9)}


def test_idle_under_needs_a_device():
    host_only = [p for p in _planes() if p["name"].startswith("/host:")]
    assert spans.idle_under(host_only, ["train.feed"], 1) == {}


# -- traces recorded on the chip --------------------------------------------------


def _in_run_dir(tmp: pathlib.Path, src: pathlib.Path) -> pathlib.Path:
    """A run directory holding a copy of ``src`` where a traced run puts its
    profile; ``hlo_stats`` writes its cache beside the copy."""
    dest = tmp / "trace" / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(src, dest / src.name)
    return tmp


def _ctx(run_dir: pathlib.Path) -> dict:
    path = spans.trace_file(run_dir)
    return {"cell": types.SimpleNamespace(run_dir=run_dir), "n_chips": 1,
            "trace": trace.summarize(trace.load(path), n_chips=1),
            "counters": {}}


def test_recorded_step_spans_and_scopes(tmp_path):
    run_dir = _in_run_dir(tmp_path, STEP_TRACE)
    path = spans.trace_file(run_dir)
    planes = trace.load(path)
    s = trace.summarize(planes, n_chips=1)
    idle = spans.idle_under(planes, ["train.feed", "train.sync"], 1)
    assert set(idle) == {"train.feed", "train.sync"}
    assert 0 < idle["train.feed"] + idle["train.sync"] <= s["window_s"] - s["busy_s"]
    fwd_bwd = spans.scope_seconds(path, "krnl_flash_attn", 1)
    bwd = spans.scope_seconds(path, "krnl_flash_attn_bwd", 1)
    assert 0 < bwd < fwd_bwd <= s["busy_s"]
    assert spans.scope_seconds(path, "krnl_no_such_kernel", 1) is None


def test_readers_of_the_recorded_step(tmp_path):
    ctx = _ctx(_in_run_dir(tmp_path, STEP_TRACE))
    got = {n: harness.load_reader(n)(ctx) for n in TRAIN_READERS}
    assert all(0 < v < 100 for v in got.values()), got
    idle = 100 * (1 - ctx["trace"]["busy_s"] / ctx["trace"]["window_s"])
    assert got["train_feed_share"] + got["train_sync_share"] <= idle


def test_readers_find_nothing_in_a_trace_without_spans_or_scopes(tmp_path):
    """What the parent program records: the readers return nothing."""
    ctx = _ctx(_in_run_dir(tmp_path, OLD_TRACE))
    assert {n: harness.load_reader(n)(ctx) for n in TRAIN_READERS} == dict.fromkeys(
        TRAIN_READERS)


# -- on the CPU ----------------------------------------------------------------------


def test_train_readers_find_nothing_on_the_cpu(tmp_path):
    """What a traced run on the CPU hands the readers: a profile with no
    device plane, which :func:`bench.trace.summarize` reduces to nothing.
    (A whole tiny train run costs about 20 s of cold compiles here and
    reaches the readers with just this.)"""
    run_dir = _in_run_dir(tmp_path, STEP_TRACE)
    planes = [p for p in trace.load(spans.trace_file(run_dir))
              if not p["name"].startswith("/device:")]
    assert trace.summarize(planes, n_chips=1) is None
    ctx = {"cell": types.SimpleNamespace(run_dir=run_dir), "n_chips": 1,
           "trace": None, "counters": {}}
    assert {n: harness.load_reader(n)(ctx) for n in TRAIN_READERS} == dict.fromkeys(
        TRAIN_READERS)


def test_tune_readers_scale_the_window_build(monkeypatch):
    """The tune readers on the counters the tune driver keeps, after a real
    trial of an interpret-mode kernel. (The tiny tune cell's whole run
    costs about 8 s of engine set-up and trials here; run beside the tune
    cell's own tests, whose 3-4 s windows one tiny job nearly fills, it
    made those fail.)"""
    from repro.tuning import evaluator
    from repro.tuning.kernel_objective import KernelTuneEvaluator

    monkeypatch.setattr(evaluator, "PHASE_TOTALS", dict.fromkeys(
        evaluator.PHASE_TOTALS, 0))
    _, meta = KernelTuneEvaluator("rmsnorm", {"rows": 16, "D": 128}, iters=2)(
        {"block_rows": 8})
    ctx = {"counters": {"attempted": 1, "build_s": meta["build_seconds"],
                        "window_s": 2 * meta["build_seconds"]}}
    got = {n: harness.load_reader(n)(ctx) for n in TUNE_READERS}
    assert got == {"tune_lower_share": pytest.approx(50 * meta["lower_seconds"]
                                                     / meta["build_seconds"]),
                   "tune_compile_share": pytest.approx(
                       50 * meta["compile_seconds"] / meta["build_seconds"])}
    assert 0 < got["tune_lower_share"] + got["tune_compile_share"] <= 50


@pytest.mark.parametrize("name", TUNE_READERS)
def test_tune_readers_find_nothing_without_the_counters(name, monkeypatch):
    from repro.tuning import evaluator

    monkeypatch.delattr(evaluator, "PHASE_TOTALS")
    ctx = {"counters": {"attempted": 3, "build_s": 1.0, "window_s": 2.0}}
    assert harness.load_reader(name)(ctx) is None
