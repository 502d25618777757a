"""Batched ask/tell contract + parallel evaluation executor tests.

The golden fixture ``tests/golden/ask_tell_traces.json`` was captured
from the pre-batching single-point Tuner loop, so the ``parallelism=1``
tests pin bit-for-bit backward compatibility of the refactor.
"""
import json
import math
import pathlib
import time

import numpy as np
import pytest

from repro.core import (ENGINES, ExecutorConfig, Observation, SearchSpace,
                        Tuner, TunerConfig)
from repro.tuning.executor import EvalResult, EvaluationExecutor, MemoCache
from repro.tuning.objective import Evaluator, FunctionEvaluator, as_evaluator

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


def evaluate_all(ex, points):
    """Submit ``points`` and wait for all of them; results in submission
    order."""
    pendings = ex.submit(points)
    for _ in ex.as_completed(pendings):
        pass
    return [p.result() for p in pendings]


# ---------------------------------------------------------------------------
# ask/tell contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ALGOS)
def test_ask_batches_are_deterministic_and_deduped(algo):
    def batches(seed):
        space = golden_space()
        engine = ENGINES[algo](space, seed=seed)
        from repro.core import History
        h = History(space)
        out = []
        for _ in range(4):
            batch = engine.ask(5, h)
            assert batch, "ask returned an empty batch with grid remaining"
            keys = [space.key(p) for p in batch]
            assert len(set(keys)) == len(keys), f"duplicate points in batch: {batch}"
            out.append([dict(p) for p in batch])
            engine.tell([Observation(point=p, value=golden_objective(p))
                         for p in batch])
            for p in batch:
                h.add(p, golden_objective(p))
        return out
    assert batches(7) == batches(7)  # same seed -> same batches
    if algo != "exhaustive":  # the grid sweep is seed-independent by design
        assert batches(7) != batches(8)  # different seed explores differently


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("seed", [0, 3])
def test_parallelism_1_reproduces_seed_trace(algo, seed):
    """The refactored loop at parallelism=1 is bit-for-bit the old loop."""
    trace = GOLDEN["traces"][f"{algo}:{seed}"]
    t = Tuner(golden_objective, golden_space(),
              TunerConfig(algorithm=algo, budget=18, seed=seed,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=1)))
    h = t.run()
    assert h.points() == trace["points"]
    assert [e.value for e in h.evals] == pytest.approx(trace["values"])


@pytest.mark.parametrize("algo", ["random", "exhaustive"])
def test_parallel_matches_sequential_best(algo):
    """Engines whose batch is just n sequential draws find the same best."""
    def run(par):
        t = Tuner(golden_objective, golden_space(),
                  TunerConfig(algorithm=algo, budget=24, seed=5,
                              verbose=False,
                              executor=ExecutorConfig(parallelism=par)))
        h = t.run()
        t.close()
        return h
    h1, h4 = run(1), run(4)
    assert len(h4) == 24
    assert h4.best().value == pytest.approx(h1.best().value)


@pytest.mark.parametrize("algo", ["bo", "ga", "nms", "random"])
def test_parallel_batches_reach_comparable_best(algo):
    """parallelism=4 spends the same budget and still finds a good optimum.

    (Exhaustive is excluded: 24 grid points in enumeration order make no
    attempt to find the optimum.)
    """
    t = Tuner(golden_objective, golden_space(),
              TunerConfig(algorithm=algo, budget=24, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(parallelism=4)))
    h = t.run()
    t.close()
    assert len(h) == 24
    # global max of the objective is ~68.6; any sane search lands near it
    assert h.best().value >= 50.0


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

def test_executor_orders_results_and_memoizes():
    space = golden_space()
    calls = []

    def obj(p):
        calls.append(space.key(p))
        return float(p["inter_op"])

    ex = EvaluationExecutor(obj, space, parallelism=2, backend="thread")
    pts = [{"inter_op": i, "intra_op": 0, "build": 1} for i in (3, 1, 2)]
    out = evaluate_all(ex, pts)
    assert [r.value for r in out] == [3.0, 1.0, 2.0]  # submission order
    out2 = evaluate_all(ex, pts)  # second pass: pure cache hits
    assert [r.value for r in out2] == [3.0, 1.0, 2.0]
    assert all(r.meta.get("memoized") for r in out2)
    assert len(calls) == 3
    ex.close()


def test_executor_failure_isolation():
    """A crashing configuration scores -inf; the pool survives and keeps
    evaluating (the paper's failed-run semantics)."""
    space = golden_space()

    def obj(p):
        if p["inter_op"] % 2 == 0:
            raise RuntimeError("OOM")
        return 1.0

    ex = EvaluationExecutor(obj, space, parallelism=3, backend="thread")
    pts = [{"inter_op": i, "intra_op": 0, "build": 1} for i in range(1, 9)]
    out = evaluate_all(ex, pts)
    assert [r.value for r in out] == [1.0, -math.inf] * 4
    assert all("error" in r.meta for r in out if r.value == -math.inf)
    # pool still alive for the next batch
    more = evaluate_all(ex, [{"inter_op": 9, "intra_op": 0, "build": 1}])
    assert more[0].value == 1.0
    ex.close()


def test_executor_timeout_scores_neg_inf():
    space = golden_space()

    def obj(p):
        if p["inter_op"] == 1:
            time.sleep(30)
        return 1.0

    ex = EvaluationExecutor(obj, space, parallelism=2, backend="thread",
                            timeout=0.3)
    out = evaluate_all(ex, [{"inter_op": 1, "intra_op": 0, "build": 1},
                            {"inter_op": 2, "intra_op": 0, "build": 1}])
    assert out[0].value == -math.inf and out[0].meta.get("timeout")
    assert out[1].value == 1.0
    ex.close()


def test_executor_timeout_queued_task_not_poisoned():
    """A task still queued when its wait expires was never measured: it must
    be run inline, not recorded (and memoized!) as a failure."""
    space = golden_space()

    def obj(p):
        if p["inter_op"] == 1:
            time.sleep(30)
        return float(p["inter_op"])

    ex = EvaluationExecutor(obj, space, parallelism=1, backend="thread",
                            timeout=0.3)
    out = evaluate_all(ex, [{"inter_op": 1, "intra_op": 0, "build": 1},
                            {"inter_op": 2, "intra_op": 0, "build": 1}])
    assert out[0].value == -math.inf and out[0].meta.get("timeout")
    assert out[1].value == 2.0 and "timeout" not in out[1].meta
    ex.close()


def test_timeout_implies_pool_backend():
    """--eval-timeout must bound running evaluations even at parallelism=1,
    which the serial backend cannot do."""
    space = golden_space()
    ex = EvaluationExecutor(lambda p: 1.0, space, parallelism=1, timeout=0.2)
    assert ex.backend == "thread"
    ex.close()
    # without a timeout, parallelism=1 keeps the bit-for-bit serial path
    assert EvaluationExecutor(lambda p: 1.0, space, parallelism=1).backend == "serial"


def test_executor_duplicate_points_evaluated_once():
    space = golden_space()
    calls = []

    def obj(p):
        calls.append(1)
        return 1.0

    ex = EvaluationExecutor(obj, space, parallelism=1)
    p = {"inter_op": 1, "intra_op": 0, "build": 1}
    out = evaluate_all(ex, [p, dict(p), dict(p)])
    assert len(calls) == 1
    assert [r.value for r in out] == [1.0, 1.0, 1.0]


def test_memo_cache_process_safe_roundtrip():
    cache = MemoCache.process_safe()
    cache.put(("k",), EvalResult({"a": 1}, 2.0, 0.1, {"m": 1}))
    hit = cache.get(("k",))
    assert hit.value == 2.0 and hit.meta == {"m": 1}
    assert cache.get(("missing",)) is None
    assert len(cache) == 1


def test_process_backend_with_picklable_objective():
    space = golden_space()
    ex = EvaluationExecutor(golden_objective, space, parallelism=2,
                            backend="process")
    pts = space.sample(np.random.default_rng(0), 3)
    out = evaluate_all(ex, pts)
    assert [r.value for r in out] == [
        pytest.approx(golden_objective(p)) for p in pts]
    ex.close()


def test_process_backend_refuses_when_parent_holds_tpu(monkeypatch):
    """A chip belongs to one process: a child the pool starts would fail
    or hang, so the pool refuses before starting one."""
    from repro.tuning import objective

    assert not objective.parent_holds_tpu()  # the CPU backend holds no chip
    monkeypatch.setattr(objective, "parent_holds_tpu", lambda: True)
    space = golden_space()
    ex = EvaluationExecutor(golden_objective, space, parallelism=2,
                            backend="process")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        evaluate_all(ex, space.sample(np.random.default_rng(0), 2))
    ex.close()


# ---------------------------------------------------------------------------
# tuner integration: budgets, checkpointing, protocol
# ---------------------------------------------------------------------------

def test_nms_resume_with_speculative_batches_matches_uninterrupted():
    """Replaying a checkpoint must not feed unconsumed speculative probes
    into the NMS state machine: a resumed run continues exactly like an
    uninterrupted one (NMS only draws rng at init, so traces are equal).
    Pinned to parallelism=1, where the completion-driven loop's trace is
    deterministic; NMS reconciliation of out-of-order speculative probes
    is covered in test_async_loop.py."""
    def run_to(budget, ck=None):
        t = Tuner(golden_objective, golden_space(),
                  TunerConfig(algorithm="nms", budget=budget, seed=1,
                              verbose=False, checkpoint_path=ck,
                              executor=ExecutorConfig(parallelism=1)))
        h = t.run()
        t.close()
        return h

    full = run_to(24)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        ck = str(pathlib.Path(d) / "nms.json")
        run_to(12, ck)
        resumed = run_to(24, ck)
    assert resumed.points() == full.points()
    assert [e.value for e in resumed.evals] == pytest.approx(
        [e.value for e in full.evals])


def test_exhaustive_grid_exhaustion_ends_cleanly():
    """budget > grid: the sweep completes and the tuner stops, no crash."""
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


def test_wall_clock_budget_stops_early():
    def obj(p):
        time.sleep(0.02)
        return golden_objective(p)

    t = Tuner(obj, golden_space(),
              TunerConfig(algorithm="random", budget=10_000, seed=0,
                          verbose=False, wall_clock_budget=0.4,
                          executor=ExecutorConfig(parallelism=2)))
    t0 = time.time()
    h = t.run()
    t.close()
    assert 0 < len(h) < 10_000
    assert time.time() - t0 < 5.0


def test_evaluator_protocol_explicit():
    # plain scalar callables are adapted
    ev = as_evaluator(lambda p: 3)
    assert isinstance(ev, FunctionEvaluator)
    assert ev({"x": 1}) == (3.0, {})
    # evaluators with returns_meta pass through untouched
    class My(Evaluator):
        def __call__(self, p):
            return 1.0, {"tag": "m"}
    m = My()
    assert as_evaluator(m) is m
    # tuple returns from plain callables are a loud error, not duck-typing
    with pytest.raises(TypeError, match="returns_meta"):
        as_evaluator(lambda p: (1.0, {}))({"x": 1})


def test_tuner_records_meta_from_evaluator():
    class My(Evaluator):
        def __call__(self, p):
            return float(p["inter_op"]), {"tag": p["inter_op"]}

    t = Tuner(My(), golden_space(),
              TunerConfig(algorithm="random", budget=4, seed=0,
                          verbose=False))
    h = t.run()
    assert all(e.meta["tag"] == e.point["inter_op"] for e in h.evals)


def test_evaluator_declared_cost_overrides_wall_clock():
    """meta["cost_seconds"] is recorded as the evaluation cost (the signal
    cost-aware acquisition trains on), overriding the wall-clock timing;
    bogus declarations fall back to the measured time."""
    class Declared(Evaluator):
        def __call__(self, p):
            return 1.0, {"cost_seconds": 7.5}

    ex = EvaluationExecutor(Declared(), golden_space(), parallelism=1)
    out = evaluate_all(ex, [{"inter_op": 1, "intra_op": 0, "build": 1}])
    ex.close()
    assert out[0].cost_seconds == 7.5
    assert out[0].meta["cost_seconds"] == 7.5

    class Bogus(Evaluator):
        def __call__(self, p):
            time.sleep(0.01)
            return 1.0, {"cost_seconds": -3.0}

    ex = EvaluationExecutor(Bogus(), golden_space(), parallelism=1)
    out = evaluate_all(ex, [{"inter_op": 1, "intra_op": 0, "build": 1}])
    ex.close()
    assert out[0].cost_seconds >= 0.01  # fell back to wall clock
