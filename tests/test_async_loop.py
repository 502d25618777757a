"""Completion-driven tuner loop: out-of-order completion, mid-stream
checkpoint/resume with stale in-flight points, NMS speculative-probe
reconciliation when probes complete late, and wall-clock bounding of
in-flight work.

Parallel completion *order* is inherently nondeterministic, so these
tests assert semantic invariants (value/point consistency, budget
accounting, state-machine equivalence, uniqueness after resume) rather
than full trace equality; bit-for-bit trace pinning lives in
test_executor.py at ``parallelism=1``.
"""
import json
import math
import pathlib
import time

import pytest

from repro.core import (ENGINES, ExecutorConfig, History, Observation, Tuner,
                        TunerConfig)
from repro.core.space import SearchSpace

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "ask_tell_traces.json")
    .read_text())

ALGOS = ["bo", "ga", "nms", "random", "exhaustive"]


def golden_space() -> SearchSpace:
    return SearchSpace.from_dicts(GOLDEN["space"])


def golden_objective(p):
    a, b, c = p["inter_op"], p["intra_op"], p["build"]
    return float(50.0 * pow(2.718281828, -((a - 11) / 5.0) ** 2)
                 + 0.3 * b - 0.004 * (b - 25) ** 2 + 7.0 * c)


def skewed_objective(p):
    """Deterministic value with a skewed simulated measurement cost: a
    quarter of the grid is 10x slower, which is exactly the shape that
    stalls a batch-barrier loop."""
    if (p["inter_op"] + p["intra_op"]) % 4 == 0:
        time.sleep(0.10)
    else:
        time.sleep(0.01)
    return golden_objective(p)


def evaluate_all(ex, points):
    """Submit ``points`` and wait for all of them; results in submission
    order."""
    pendings = ex.submit(points)
    for _ in ex.as_completed(pendings):
        pass
    return [p.result() for p in pendings]


# ---------------------------------------------------------------------------
# completion-driven loop semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("seed", [0, 3])
def test_async_parallelism_1_reproduces_seed_trace(algo, seed):
    """The completion-driven loop at parallelism=1 degenerates to the
    historical sequential loop, bit-for-bit."""
    trace = GOLDEN["traces"][f"{algo}:{seed}"]
    t = Tuner(golden_objective, golden_space(),
              TunerConfig(algorithm=algo, budget=18, seed=seed,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=1)))
    h = t.run()
    assert h.points() == trace["points"]
    assert [e.value for e in h.evals] == pytest.approx(trace["values"])


@pytest.mark.parametrize("algo", ["bo", "ga", "nms", "random"])
def test_async_out_of_order_results_stay_consistent(algo):
    """With skewed costs, completions land out of submission order; every
    recorded (point, value) pair must still correspond, the budget must be
    spent exactly, and no in-flight marks may survive the run."""
    t = Tuner(skewed_objective, golden_space(),
              TunerConfig(algorithm=algo, budget=16, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=4)))
    h = t.run()
    t.close()
    assert len(h) == 16
    assert h.n_pending() == 0
    for e in h.evals:
        assert e.value == pytest.approx(golden_objective(e.point))
    # slow vs fast cost attribution survived the reordering
    paid = [e for e in h.evals if e.cost_seconds > 0]
    assert paid, "no evaluation recorded its measurement cost"
    assert h.best().value >= 50.0


def test_async_cost_seconds_reach_engine():
    """Measured evaluation cost is threaded through tell so engines can be
    wall-clock-aware."""
    t = Tuner(skewed_objective, golden_space(),
              TunerConfig(algorithm="random", budget=6, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=2)))
    t.run()
    t.close()
    assert t.engine.mean_cost_seconds > 0.0


def test_async_checkpoint_resume_mid_stream_with_stale_inflight(tmp_path):
    """Abort while several skew-delayed evaluations are in flight: the
    checkpoint holds only completed results, stale in-flight points leave
    no pending marks, and a resumed run finishes the budget without
    re-measuring anything it already has."""
    ck = tmp_path / "t.json"
    state = {"evals": 0}

    def obj(p):
        state["evals"] += 1
        if state["evals"] == 7:
            raise KeyboardInterrupt()  # not failure-isolated: a real abort
        return skewed_objective(p)

    t1 = Tuner(obj, golden_space(),
               TunerConfig(algorithm="random", budget=16, seed=2,
                           verbose=False,
                           executor=ExecutorConfig(parallelism=1),
                           checkpoint_path=str(ck)))
    with pytest.raises(KeyboardInterrupt):
        t1.run()
    assert 0 < len(t1.history) < 16
    assert t1.history.n_pending() == 0  # stale in-flight marks cleaned up
    saved = json.loads(ck.read_text())
    assert len(saved) == len(t1.history)
    assert [r["point"] for r in saved] == t1.history.points()

    t2 = Tuner(golden_objective, golden_space(),
               TunerConfig(algorithm="random", budget=16, seed=2,
                           verbose=False,
                           executor=ExecutorConfig(parallelism=4),
                           checkpoint_path=str(ck)))
    h2 = t2.run()
    t2.close()
    assert len(h2) == 16
    assert h2.points()[:len(t1.history)] == t1.history.points()
    keys = {golden_space().key(p) for p in h2.points()}
    assert len(keys) == 16  # nothing measured twice after the resume


def test_async_wall_clock_bounds_inflight_work():
    """A hung evaluation must not blow past wall_clock_budget: work still
    unfinished at the deadline is abandoned — the run ends on time and the
    hung configuration is NOT falsely recorded as a failure (a deadline is
    a budget artifact of this run, not a property of the point)."""
    def obj(p):
        if p["inter_op"] == 1:
            time.sleep(8)  # hung measurement
        return golden_objective(p)

    space = golden_space()
    t = Tuner(obj, space,
              TunerConfig(algorithm="exhaustive", budget=10_000, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=2),
                          wall_clock_budget=0.6))
    t0 = time.time()
    h = t.run()
    t.close()
    elapsed = time.time() - t0
    assert elapsed < 5.0, f"hung eval blew past the wall clock ({elapsed:.1f}s)"
    assert h.n_pending() == 0
    hung = [e for e in h.evals if e.point["inter_op"] == 1]
    assert not hung, f"abandoned eval falsely recorded: {hung}"
    assert all(math.isfinite(e.value) for e in h.evals)


def test_wall_clock_bounds_hung_eval_even_at_parallelism_1():
    """The serial backend cannot abandon a running evaluation, so a
    wall-clock budget must select a pool backend even at parallelism=1."""
    def obj(p):
        time.sleep(8)
        return 1.0

    t = Tuner(obj, golden_space(),
              TunerConfig(algorithm="random", budget=10, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=1),
                          wall_clock_budget=0.5))
    assert t.executor.backend == "thread"
    t0 = time.time()
    h = t.run()
    t.close()
    assert time.time() - t0 < 5.0
    assert len(h) == 0 and h.n_pending() == 0

    # the same contract must hold when the budget arrives at run() time
    t2 = Tuner(obj, golden_space(),
               TunerConfig(algorithm="random", budget=10, seed=0,
                           verbose=False,
                           executor=ExecutorConfig(parallelism=1)))
    assert t2.executor.backend == "serial"
    t0 = time.time()
    h2 = t2.run(wall_clock=0.5)
    t2.close()
    assert t2.executor.backend == "thread"  # swapped before the loop started
    assert time.time() - t0 < 5.0
    assert len(h2) == 0 and h2.n_pending() == 0


def test_eval_timeout_verdict_not_persisted_to_disk(tmp_path):
    """A per-eval timeout scores -inf for this run but must not poison the
    cross-run disk cache: a later run (maybe with a larger timeout) gets to
    measure the configuration for real."""
    memo = str(tmp_path / "memo.json")
    calls = {"n": 0}

    def obj(p):
        calls["n"] += 1
        if p["inter_op"] == 1 and calls["n"] == 1:
            time.sleep(8)  # hung only on the first attempt
        return golden_objective(p)

    space = golden_space()
    pts = [{"inter_op": 1, "intra_op": 0, "build": 1}]
    from repro.tuning.executor import EvaluationExecutor

    ex1 = EvaluationExecutor(obj, space, parallelism=1, timeout=0.3,
                             cache_path=memo)
    out = evaluate_all(ex1, pts)
    ex1.close()
    assert out[0].value == -math.inf and out[0].meta.get("timeout")
    # the -inf verdict is memoized for THIS executor...
    assert ex1.cache.get(space.key(pts[0])) is not None
    # ...but a fresh run from the same disk cache re-measures, and succeeds
    ex2 = EvaluationExecutor(obj, space, parallelism=1, timeout=5.0,
                             cache_path=memo)
    out2 = evaluate_all(ex2, pts)
    ex2.close()
    assert out2[0].value == pytest.approx(golden_objective(pts[0]))
    assert not out2[0].meta.get("memoized")


def test_async_engine_exhaustion_ends_cleanly():
    from repro.core import IntDim
    space = SearchSpace([IntDim("a", 0, 3, 1)])
    t = Tuner(lambda p: float(p["a"]), space,
              TunerConfig(algorithm="exhaustive", budget=100, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=3)))
    h = t.run()
    t.close()
    assert len(h) == 4  # the whole grid, exactly once
    assert h.best().point["a"] == 3


# ---------------------------------------------------------------------------
# NMS speculative probes completing late
# ---------------------------------------------------------------------------

def _drive(engine, tell_order, budget=30):
    """Run an engine manually, telling each batch in a caller-chosen order;
    returns the sequence of asked batches (keyed)."""
    space = engine.space
    h = History(space)
    asked = []
    while len(h) < budget:
        batch = engine.ask(4, h)
        if not batch:
            break
        asked.append([space.key(p) for p in batch])
        results = [(p, golden_objective(p)) for p in batch]
        for p, v in tell_order(results):
            engine.tell([Observation(point=p, value=v)])  # completion order
            h.add(p, v)
    return asked


def test_nms_late_speculative_probes_reconcile():
    """Telling speculative probes before their primary (worst-case
    completion order) must leave the NMS state machine in the same state
    as in-order completion: the asked-batch sequences stay identical."""
    in_order = _drive(ENGINES["nms"](golden_space(), seed=1),
                      lambda results: results)
    reversed_ = _drive(ENGINES["nms"](golden_space(), seed=1),
                       lambda results: list(reversed(results)))
    assert in_order == reversed_


def test_nms_probe_arriving_before_primary_is_buffered():
    """A speculative probe told before the primary is buffered, not lost:
    once the primary arrives, both are consumed and the machine advances
    (the next ask changes)."""
    space = golden_space()
    eng = ENGINES["nms"](space, seed=1)
    h = History(space)
    # finish init so the machine is in the reflect phase with speculation
    while eng._phase == "init":
        batch = eng.ask(4, h)
        for p in batch:
            eng.tell([Observation(point=p, value=golden_objective(p))])
            h.add(p, golden_objective(p))
    batch = eng.ask(4, h)
    assert len(batch) >= 2, "reflect phase should speculate"
    primary, probes = batch[0], batch[1:]
    before = space.key(eng._primary())
    # late primary: tell every probe first — machine must not advance
    for p in probes:
        eng.tell([Observation(point=p, value=golden_objective(p))])
        h.add(p, golden_objective(p))
    assert space.key(eng._primary()) == before
    assert all(space.key(p) in eng._told for p in probes)
    # primary lands: machine advances, consuming buffered probes it needs
    eng.tell([Observation(point=primary, value=golden_objective(primary))])
    h.add(primary, golden_objective(primary))
    assert space.key(eng._primary()) != before
