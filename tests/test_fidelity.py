"""Multi-fidelity evaluation subsystem: rung scheduler (ASHA successive
halving), fidelity-aware executor plumbing (memo keying, preemption
races), the variance-adaptive wall-clock evaluator, and the tuner's
multi-fidelity loop.

The preemption tests pin the cancellation race explicitly (satellite of
ISSUE 4): ``future.cancel()`` may return False because a worker already
started — the preemption path must handle both outcomes without losing
or double-recording a result.
"""
import json
import math
import threading
import time
import types

import pytest

from repro.core import (CatDim, ExecutorConfig, IntDim, MultiFidelityConfig,
                        SearchSpace, Tuner, TunerConfig)
from repro.tuning.executor import (
    EvaluationExecutor,
    grid_key_of,
    memo_key,
    run_objective,
)
from repro.tuning.objective import CountingEvaluator, Evaluator
from repro.tuning.schedulers import RungScheduler


def make_space() -> SearchSpace:
    return SearchSpace([IntDim("inter_op", 1, 16),
                        IntDim("intra_op", 0, 60, 5),
                        CatDim("build", (1, 2, 3))])


def value_of(p):
    a, b, c = p["inter_op"], p["intra_op"], p["build"]
    return float(50.0 * pow(2.718281828, -((a - 11) / 5.0) ** 2)
                 + 0.3 * b - 0.004 * (b - 25) ** 2 + 7.0 * c)


class FidelityObjective(Evaluator):
    """Deterministic objective with an honest fidelity model: cost scales
    with fidelity, value carries a point-dependent bias shrinking as
    fidelity rises."""

    supports_fidelity = True

    def __init__(self, sleep: float = 0.0):
        self.sleep = sleep
        self.calls = []  # (key, fidelity) per real invocation

    def __call__(self, p, fidelity=None):
        f = 1.0 if fidelity is None else float(fidelity)
        self.calls.append(((p["inter_op"], p["intra_op"], p["build"]), f))
        if self.sleep:
            time.sleep(self.sleep * f)
        wiggle = ((p["inter_op"] * 13 + p["intra_op"] * 7) % 9 - 4) / 2.0
        return value_of(p) + (1.0 - f) * wiggle, {"cost_seconds": 0.01 * f}


# ---------------------------------------------------------------------------
# RungScheduler unit behavior
# ---------------------------------------------------------------------------

def test_ladder_is_geometric_in_eta():
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    assert [round(s.fidelity(r), 6) for r in range(s.n_rungs)] == [
        round(1 / 9, 6), round(1 / 3, 6), 1.0]
    assert s.base_fidelity == pytest.approx(1 / 9)
    assert s.is_top(2) and not s.is_top(1)
    # degenerate ladder: min == max -> single full-fidelity rung
    assert RungScheduler(eta=3.0, min_fidelity=1.0).n_rungs == 1


def test_promotion_needs_eta_completions_and_top_quantile():
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    p = {"x": 1}
    s.on_result(("a",), p, 10.0, 0)
    s.on_result(("b",), p, 5.0, 0)
    assert s.next_promotion() is None  # rung too small to rank
    s.on_result(("c",), p, 1.0, 0)
    point, rung = s.next_promotion()
    assert rung == 1  # best of the rung promotes first
    assert s.next_promotion() is None  # only top floor(3/3)=1 promotable
    # rung grows: floor(6/3)=2 -> the second-best becomes promotable
    for k, v in [("d", 0.5), ("e", 0.25), ("f", 0.125)]:
        s.on_result((k,), p, v, 0)
    _, rung = s.next_promotion()
    assert rung == 1
    assert s.next_promotion() is None


def test_promotion_prefers_deepest_rung_and_skips_failures():
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    p = {"x": 1}
    for k, v in [("a", 3.0), ("b", 2.0), ("c", 1.0)]:
        s.on_result((k,), p, v, 0)
    for k, v in [("a", 3.1), ("d", 2.5), ("e", 0.1)]:
        s.on_result((k,), p, v, 1)
    _, rung = s.next_promotion()
    assert rung == 2  # the rung-1 survivor outranks rung-0 promotions
    # -inf (failed) results never promote
    s2 = RungScheduler(eta=3.0, min_fidelity=0.1)
    for k in "abc":
        s2.on_result((k,), p, -math.inf, 0)
    assert s2.next_promotion() is None


def test_dominated_tracks_rising_cutoff_and_preempt_returns_key():
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    p = {"x": 1}
    for k, v in [("a", 10.0), ("b", 9.0), ("c", 1.0)]:
        s.on_result((k,), p, v, 0)
    point, rung = s.next_promotion()  # "a" promotes at value 10
    assert not s.dominated(("a",), rung)
    # six better results land: cutoff rises past 10 -> "a" is outclassed
    for k, v in [("d", 20.0), ("e", 19.0), ("f", 18.0),
                 ("g", 17.0), ("h", 16.0), ("i", 15.0)]:
        s.on_result((k,), p, v, 0)
    assert s.dominated(("a",), rung)
    # a cancelled preemption returns the key to the unpromoted pool and
    # counts on the target rung (whose start it cancels), so per-rung
    # stats reconcile: started = completed + preempted + in-flight
    s.on_preempted(("a",), rung)
    assert s.rungs[rung].n_preempted == 1
    assert ("a",) not in s.rungs[0].promoted
    # bottom-rung submissions carry no prior value: never dominated
    assert not s.dominated(("z",), 0)


# ---------------------------------------------------------------------------
# executor: fidelity plumbing
# ---------------------------------------------------------------------------

def test_run_objective_forwards_fidelity_only_when_supported():
    fid_obj = FidelityObjective()
    v, _s, meta = run_objective(fid_obj, {"inter_op": 1, "intra_op": 0,
                                          "build": 1}, 0.25)
    assert meta["fidelity"] == 0.25 and fid_obj.calls[0][1] == 0.25
    # plain callables are silently upgraded to a full measurement
    v2, _s, meta2 = run_objective(
        CountingEvaluator(lambda p: 7.0).inner, {"x": 1}, 0.25)
    assert v2 == 7.0 and meta2["fidelity"] == 1.0
    # full-fidelity calls keep the historical meta exactly (golden traces)
    _v, _s, meta3 = run_objective(fid_obj, {"inter_op": 1, "intra_op": 0,
                                            "build": 1}, None)
    assert "fidelity" not in meta3 or meta3["fidelity"] == 1.0


def test_memo_key_separates_fidelities_and_roundtrips_grid_key():
    gk = (1, 0, "x")
    assert memo_key(gk, None) == gk == memo_key(gk, 1.0)
    low = memo_key(gk, 1 / 3)
    assert low != gk and grid_key_of(low) == gk and grid_key_of(gk) == gk
    assert memo_key(gk, 1 / 3) == low  # stable across calls


def test_partial_results_never_served_for_full_requests(tmp_path):
    space = make_space()
    obj = FidelityObjective()
    memo = str(tmp_path / "memo.json")
    ex = EvaluationExecutor(obj, space, parallelism=1, cache_path=memo)
    p = {"inter_op": 11, "intra_op": 60, "build": 3}
    low = ex.next_completed(ex.submit([p], fidelity=1 / 9, rung=0)).result()
    full = ex.next_completed(ex.submit([p])).result()
    assert not full.meta.get("memoized")  # the cheap result was not reused
    assert full.value == pytest.approx(value_of(p))
    assert low.value != pytest.approx(full.value)  # bias is real
    # same-fidelity repeat IS a memo hit
    again = ex.next_completed(ex.submit([p], fidelity=1 / 9, rung=0)).result()
    assert again.meta.get("memoized")
    ex.close()
    assert len(obj.calls) == 2
    # the disk store reloads both entries under their own fidelity keys
    ex2 = EvaluationExecutor(FidelityObjective(), space, parallelism=1,
                             cache_path=memo)
    assert ex2.next_completed(
        ex2.submit([p], fidelity=1 / 9)).result().meta.get("memoized")
    assert ex2.next_completed(ex2.submit([p])).result().meta.get("memoized")
    ex2.close()


# ---------------------------------------------------------------------------
# executor: the preemption cancellation race (both outcomes)
# ---------------------------------------------------------------------------

def test_preempt_cancels_queued_eval_without_poisoning():
    """future.cancel() True: the task never ran — nothing recorded,
    nothing cached, and a later submit measures it for real."""
    space = make_space()
    release = threading.Event()
    calls = []

    class Blocking(Evaluator):
        supports_fidelity = True

        def __call__(self, p, fidelity=None):
            calls.append(p["inter_op"])
            release.wait(5)
            return float(p["inter_op"]), {}

    ex = EvaluationExecutor(Blocking(), space, parallelism=1,
                            backend="thread")
    pa = {"inter_op": 1, "intra_op": 0, "build": 1}
    pb = {"inter_op": 2, "intra_op": 0, "build": 1}
    (pend_a,) = ex.submit([pa], fidelity=1 / 3, rung=1)
    (pend_b,) = ex.submit([pb], fidelity=1 / 3, rung=1)  # queued behind a
    assert ex.preempt(pend_b) == "cancelled"
    assert pend_b.done() and pend_b.result().meta.get("preempted")
    release.set()
    done = ex.next_completed([pend_a])
    assert done is pend_a and done.result().value == 1.0
    # b never ran, was not cached, and can be measured later
    assert calls == [1]
    (pend_b2,) = ex.submit([pb], fidelity=1 / 3, rung=1)
    r = ex.next_completed([pend_b2]).result()
    assert r.value == 2.0 and not r.meta.get("memoized")
    assert calls == [1, 2]
    ex.close()


def test_preempt_of_started_eval_records_exactly_once():
    """future.cancel() False: a worker already started — the measurement
    finishes and is recorded exactly once, not lost, not duplicated."""
    space = make_space()
    started = threading.Event()
    release = threading.Event()

    class Signalling(Evaluator):
        supports_fidelity = True

        def __call__(self, p, fidelity=None):
            started.set()
            release.wait(5)
            return 42.0, {}

    ex = EvaluationExecutor(Signalling(), space, parallelism=1,
                            backend="thread")
    (pend,) = ex.submit([{"inter_op": 3, "intra_op": 0, "build": 1}],
                        fidelity=1 / 3, rung=1)
    assert started.wait(5), "worker never started"
    assert ex.preempt(pend) == "running"
    assert pend.preempted and not pend.done()
    release.set()
    done = ex.next_completed([pend])
    assert done is pend
    assert done.result().value == 42.0
    assert not done.result().meta.get("preempted")
    # the completed result is banked in the memo (it was paid for)
    again = ex.submit([{"inter_op": 3, "intra_op": 0, "build": 1}],
                      fidelity=1 / 3, rung=1)[0]
    assert again.done() and again.result().meta.get("memoized")
    ex.close()


def test_preempt_of_shared_future_resolves_alias_as_preempted():
    """A pending can share a running measurement with a duplicate submit
    (the stale-alias path).  Preempting one pending cancels the shared
    future; the sibling must resolve as a preempted placeholder through
    next_completed — never raise CancelledError, never record a value."""
    space = make_space()
    release = threading.Event()
    calls = []

    class Blocking(Evaluator):
        supports_fidelity = True

        def __call__(self, p, fidelity=None):
            calls.append(p["inter_op"])
            release.wait(5)
            return float(p["inter_op"]), {}

    ex = EvaluationExecutor(Blocking(), space, parallelism=1,
                            backend="thread")
    pa = {"inter_op": 1, "intra_op": 0, "build": 1}
    pb = {"inter_op": 2, "intra_op": 0, "build": 1}
    (pend_a,) = ex.submit([pa], fidelity=1 / 3)   # worker blocks on this
    (pend_b1,) = ex.submit([pb], fidelity=1 / 3)  # queued
    (pend_b2,) = ex.submit([pb], fidelity=1 / 3)  # aliases b1's future
    assert pend_b2.future is pend_b1.future
    assert ex.preempt(pend_b1) == "cancelled"
    done = ex.next_completed([pend_b2])  # must not raise CancelledError
    assert done is pend_b2
    assert done.result().meta.get("preempted")
    release.set()
    assert ex.next_completed([pend_a]).result().value == 1.0
    # nothing was measured for b; a fresh submit measures it for real
    (pend_b3,) = ex.submit([pb], fidelity=1 / 3)
    assert ex.next_completed([pend_b3]).result().value == 2.0
    assert calls == [1, 2]
    ex.close()


def test_store_reload_keys_by_requested_fidelity(tmp_path):
    """An evaluator may deliver a snapped fidelity in meta; the memo's
    lookup identity is the *requested* fidelity, so a reloaded store must
    key entries off the persisted key's fidelity tag, or a second
    identical run would re-measure every partial result."""
    space = make_space()

    class Snapping(Evaluator):
        supports_fidelity = True

        def __init__(self):
            self.calls = 0

        def __call__(self, p, fidelity=None):
            self.calls += 1
            # delivers a coarser fidelity than requested
            return 5.0, {"fidelity": 0.5}

    memo = str(tmp_path / "memo.json")
    p = {"inter_op": 9, "intra_op": 0, "build": 1}
    obj1 = Snapping()
    ex1 = EvaluationExecutor(obj1, space, parallelism=1, cache_path=memo)
    ex1.next_completed(ex1.submit([p], fidelity=1 / 9))
    ex1.close()
    assert obj1.calls == 1
    obj2 = Snapping()
    ex2 = EvaluationExecutor(obj2, space, parallelism=1, cache_path=memo)
    r = ex2.next_completed(ex2.submit([p], fidelity=1 / 9)).result()
    ex2.close()
    assert r.meta.get("memoized") and obj2.calls == 0


def test_preempt_of_completed_eval_is_noop():
    space = make_space()
    ex = EvaluationExecutor(FidelityObjective(), space, parallelism=1)
    (pend,) = ex.submit([{"inter_op": 4, "intra_op": 0, "build": 1}],
                        fidelity=1 / 9, rung=0)
    assert pend.done()  # serial backend resolves at submit
    assert ex.preempt(pend) == "done"
    assert not pend.result().meta.get("preempted")
    ex.close()


# ---------------------------------------------------------------------------
# tuner: the multi-fidelity loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["bo", "ga", "nms", "random"])
def test_multi_fidelity_loop_spends_budget_across_rungs(algo):
    obj = FidelityObjective(sleep=0.002)
    t = Tuner(obj, make_space(),
              TunerConfig(algorithm=algo, budget=8, seed=0, verbose=False,
                          executor=ExecutorConfig(parallelism=4),
                          multi_fidelity=True))
    h = t.run()
    sched = t.rung_scheduler
    t.close()
    assert h.n_pending() == 0
    fids = sorted(set(round(e.fidelity, 6) for e in h.evals))
    assert len(fids) >= 2, f"no rung mixing: {fids}"
    assert any(e.fidelity >= 1.0 for e in h.evals), "nothing reached top rung"
    spend = sum(e.fidelity for e in h.evals)
    assert spend >= 8  # logical budget spent (drain may add a little)
    # exactly-once accounting: every real call is one history row
    measured = [e for e in h.evals if not e.meta.get("memoized")]
    assert len(obj.calls) == len(measured)
    keyed = [(t_key, round(f, 9)) for t_key, f in obj.calls]
    assert len(keyed) == len(set(keyed)), "a (point, fidelity) ran twice"
    # scheduler accounting matches history
    assert sum(r["completed"] for r in sched.stats()) == len(h.evals)


def test_multi_fidelity_full_results_match_objective_exactly():
    obj = FidelityObjective()
    t = Tuner(obj, make_space(),
              TunerConfig(algorithm="random", budget=6, seed=1, verbose=False,
                          executor=ExecutorConfig(parallelism=2),
                          multi_fidelity=True))
    h = t.run()
    t.close()
    for e in h.evals:
        if e.fidelity >= 1.0:
            assert e.value == pytest.approx(value_of(e.point))
    best = h.best(full_fidelity_only=True)
    assert best.fidelity == 1.0


def test_multi_fidelity_degenerates_for_plain_callables():
    """An objective without fidelity support cannot cheapen a measurement:
    rungs would all cost the same and promotion would just re-measure
    points — the loop must fall back to the plain async loop, with every
    measurement charged and recorded as full fidelity."""
    calls = []

    def obj(p):
        calls.append(1)
        return value_of(p)

    t = Tuner(obj, make_space(),
              TunerConfig(algorithm="random", budget=4, seed=0, verbose=False,
                          executor=ExecutorConfig(parallelism=1),
                          multi_fidelity=True))
    h = t.run()
    t.close()
    assert t.rung_scheduler is None  # no rung ladder was built
    assert all(e.fidelity == 1.0 for e in h.evals)
    assert len(calls) == 4  # exactly budget full measurements, not ~9x
    assert math.isfinite(h.best(full_fidelity_only=True).value)


def test_executor_normalizes_fidelity_for_plain_callables():
    """Direct submit() callers get the same protection: a partial-fidelity
    request an evaluator cannot serve is keyed (and run) as the full
    measurement it delivers, so memo entries never fragment per rung."""
    space = make_space()
    calls = []

    def obj(p):
        calls.append(1)
        return float(p["inter_op"])

    ex = EvaluationExecutor(obj, space, parallelism=1)
    p = {"inter_op": 5, "intra_op": 0, "build": 1}
    r1 = ex.next_completed(ex.submit([p], fidelity=1 / 9, rung=0)).result()
    r2 = ex.next_completed(ex.submit([p], fidelity=1 / 3, rung=1)).result()
    r3 = ex.next_completed(ex.submit([p])).result()
    ex.close()
    assert len(calls) == 1  # one measurement served all three requests
    assert r1.value == r2.value == r3.value == 5.0
    assert r2.meta.get("memoized") and r3.meta.get("memoized")


def test_multi_fidelity_bo_gets_fidelity_feature():
    t = Tuner(FidelityObjective(), make_space(),
              TunerConfig(algorithm="bo", budget=4, seed=0, verbose=False,
                          multi_fidelity=True))
    assert t.engine.fidelity_feature
    t.close()
    # single-fidelity BO keeps the bit-for-bit surrogate path
    t2 = Tuner(lambda p: 1.0, make_space(),
               TunerConfig(algorithm="bo", budget=4, seed=0, verbose=False))
    assert not t2.engine.fidelity_feature
    t2.close()


def test_multi_fidelity_checkpoint_resume_continues_ladder(tmp_path):
    """Resuming a multi-fidelity run must rebuild rung state and budget
    accounting from the checkpoint: the budget is not re-spent from zero
    and replayed completions stay visible to the scheduler."""
    ck = tmp_path / "t.json"
    t1 = Tuner(FidelityObjective(), make_space(),
               TunerConfig(algorithm="random", budget=3, seed=2,
                           verbose=False, multi_fidelity=True,
                           executor=ExecutorConfig(parallelism=1),
                           checkpoint_path=str(ck)))
    h1 = t1.run()
    t1.close()
    n1, spend1 = len(h1), sum(e.fidelity for e in h1.evals)
    assert spend1 >= 3

    t2 = Tuner(FidelityObjective(), make_space(),
               TunerConfig(algorithm="random", budget=6, seed=2,
                           verbose=False, multi_fidelity=True,
                           executor=ExecutorConfig(parallelism=1),
                           checkpoint_path=str(ck)))
    h2 = t2.run()
    t2.close()
    assert h2.points()[:n1] == h1.points()  # replayed, not re-measured
    assert sum(e.fidelity for e in h2.evals) >= 6
    # only the remaining budget was spent (small drain slack allowed)
    assert sum(e.fidelity for e in h2.evals[n1:]) <= 6 - spend1 + 1.5
    # the scheduler saw every completion, replayed ones included
    assert sum(r["completed"] for r in t2.rung_scheduler.stats()) == len(h2)
    # replay rebuilt the promotion marks: nothing measured twice at the
    # same (point, fidelity) across the interrupt/resume boundary
    pairs = [(make_space().key(e.point), round(e.fidelity, 6))
             for e in h2.evals]
    assert len(pairs) == len(set(pairs))


def test_multi_fidelity_second_run_hits_disk_memo(tmp_path):
    memo = str(tmp_path / "memo.json")
    counting = CountingEvaluator(FidelityObjective())

    def run_once():
        t = Tuner(counting, make_space(),
                  TunerConfig(algorithm="random", budget=5, seed=3,
                              verbose=False,
                              executor=ExecutorConfig(parallelism=1,
                                                      memo_cache_path=memo),
                              multi_fidelity=MultiFidelityConfig(
                                  enabled=True, preempt=False)))
        h = t.run()
        t.close()
        return h

    run_once()
    first = counting.calls
    assert first > 0
    run_once()
    assert counting.calls == first, "second identical run re-measured"


def test_history_persists_fidelity(tmp_path):
    from repro.core import History
    space = make_space()
    h = History(space)
    p = {"inter_op": 1, "intra_op": 0, "build": 1}
    h.add(p, 1.0, 0.1, {"m": 1}, fidelity=1 / 3)
    h.add(p, 2.0, 0.3, {}, fidelity=1.0)
    path = tmp_path / "h.json"
    h.save(path)
    loaded = History.load(path, space)
    assert [e.fidelity for e in loaded.evals] == [pytest.approx(1 / 3), 1.0]
    assert loaded.best().value == 2.0
    assert list(loaded.fidelities()) == [pytest.approx(1 / 3), 1.0]
    # legacy records without a fidelity field load as full measurements
    recs = json.loads(path.read_text())
    for r in recs:
        del r["fidelity"]
    path.write_text(json.dumps(recs))
    assert [e.fidelity for e in History.load(path, space).evals] == [1.0, 1.0]


# ---------------------------------------------------------------------------
# variance-adaptive wall-clock measurement
# ---------------------------------------------------------------------------

def _make_step(point):
    import numpy as np

    def step(x):
        return x + 1

    return step, (np.zeros(4),), 4.0


def test_wallclock_adaptive_stops_early_on_stable_timing():
    from repro.tuning.evaluator import WallClockEvaluator
    ev = WallClockEvaluator(_make_step, warmup=1, rel_halfwidth=1e9,
                            min_iters=2, max_iters=12)
    v, meta = ev({"any": 1})
    assert meta["iters"] == 2  # CI target trivially met after min_iters
    assert v > 0 and meta["step_seconds"] > 0
    # an explicit full-fidelity request is byte-identical to a plain
    # call, meta keys included
    _v, meta_full = ev({"any": 1}, fidelity=1.0)
    assert "fidelity" not in meta_full
    assert sorted(meta_full) == sorted(meta)


def test_wallclock_adaptive_hits_cap_when_target_unreachable():
    from repro.tuning.evaluator import WallClockEvaluator
    ev = WallClockEvaluator(_make_step, warmup=1, rel_halfwidth=0.0,
                            min_iters=2, max_iters=7)
    _v, meta = ev({"any": 1})
    assert meta["iters"] == 7
    assert meta["ci_rel_halfwidth"] >= 0.0


def test_wallclock_fidelity_scales_iteration_cap():
    from repro.tuning.evaluator import WallClockEvaluator
    ev = WallClockEvaluator(_make_step, warmup=1, rel_halfwidth=0.0,
                            min_iters=2, max_iters=12)
    _v, meta = ev({"any": 1}, fidelity=0.25)
    assert meta["iters"] == 3  # ceil(12 * 0.25)
    assert meta["fidelity"] == 0.25


def test_wallclock_cost_is_measurement_only():
    from repro.tuning.evaluator import WallClockEvaluator
    ev = WallClockEvaluator(_make_step, warmup=3, rel_halfwidth=1e9)
    _v, meta = ev({"any": 1})
    # the timing loop is microseconds; build includes jit lowering+warmup
    # and is orders of magnitude larger — cost must exclude it
    assert meta["cost_seconds"] < meta["build_seconds"]
    assert meta["cost_seconds"] == pytest.approx(
        meta["step_seconds"] * meta["iters"], rel=1e-6)


def test_wallclock_fixed_iters_mode_unchanged():
    from repro.tuning.evaluator import WallClockEvaluator
    ev = WallClockEvaluator(_make_step, warmup=1, iters=3, adaptive=False)
    _v, meta = ev({"any": 1})
    assert meta["iters"] == 3


# ---------------------------------------------------------------------------
# roofline evaluator: shared-store re-consult on in-memory miss
# ---------------------------------------------------------------------------

def test_roofline_reconsults_store_before_recompiling(tmp_path, monkeypatch):
    import sys

    from repro.tuning.cache import JsonCacheStore
    from repro.tuning.evaluator import RooflineEvaluator
    from repro.tuning.parameters import BASELINE, config_from_point

    # any compile attempt is a test failure: the record must come from the
    # store written *after* the evaluator started
    stub = types.ModuleType("repro.launch.dryrun")

    def _no_compile(*a, **k):
        raise AssertionError("recompiled despite a store entry")

    stub.analyze_cell = _no_compile
    monkeypatch.setitem(sys.modules, "repro.launch.dryrun", stub)

    cache = str(tmp_path / "roofline.json")
    ev = RooflineEvaluator("qwen2-0.5b", "train_4k", cache_path=cache)
    assert ev._cache == {}  # store was empty at startup
    point = {"log2_dp": 1}
    rec = {"skipped": False,
           "memory": {"per_device_B": 1.0},
           "roofline": {"throughput_tok_s": 123.0}}
    # a concurrent host writes the entry after our __init__
    JsonCacheStore(cache).put(
        ev._key(config_from_point(point, BASELINE)), rec)
    value, meta = ev(point)
    assert value == 123.0
    # and the entry is now cached in memory (no second store read needed)
    assert len(ev._cache) == 1


def test_roofline_fast_fidelity_uses_distinct_cache_key(tmp_path):
    from repro.tuning.evaluator import RooflineEvaluator
    from repro.tuning.parameters import BASELINE

    ev = RooflineEvaluator("qwen2-0.5b", "train_4k",
                           cache_path=str(tmp_path / "c.json"))
    bc = BASELINE
    full_key, fast_key = ev._key(bc), ev._key(bc, fast=True)
    assert full_key != fast_key
    assert json.loads(full_key).get("analysis") is None  # legacy format kept
    assert json.loads(fast_key)["analysis"] == "fast"
