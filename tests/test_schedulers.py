"""The scheduler zoo behind the ``TrialScheduler`` seam.

Covers the seam contract itself (one base class, lifecycle hooks),
HyperBand's bracket plumbing (ladder shapes, completion-driven budget
split, replay routing), PBT's exploit/explore population (admission,
forks, doom, replay dedupe), resume equality for both new schedulers,
— parametrized over all three — the preemption race: a
``decide()``-issued preempt that lands after the completion must record
exactly once, and a PBT run surviving the death of a remote worker.
"""
import json
import threading
import time

import pytest

from repro.core import (CatDim, ExecutorConfig, IntDim, MultiFidelityConfig,
                        SearchSpace, Tuner, TunerConfig)
from repro.tuning.objective import Evaluator
from repro.tuning.remote import WorkerServer
from repro.tuning.schedulers import (HyperBandScheduler, PBTScheduler,
                                     RungScheduler, TrialScheduler,
                                     build_scheduler)


def make_space() -> SearchSpace:
    return SearchSpace([IntDim("inter_op", 1, 4),
                        IntDim("intra_op", 0, 30, 10),
                        CatDim("build", (1, 2))])


def value_of(p):
    return float(3.0 * p["inter_op"] + 0.2 * p["intra_op"] + 7.0 * p["build"])


class ForkCapable(Evaluator):
    supports_fidelity = True
    supports_fork = True

    def __init__(self):
        self.calls = []  # (key-tuple, fidelity, resume_state)

    def __call__(self, p, fidelity=None, resume_state=None):
        f = 1.0 if fidelity is None else float(fidelity)
        self.calls.append(((p["inter_op"], p["intra_op"], p["build"]), f,
                           resume_state))
        warm = int((resume_state or {}).get("warm", 0))
        return value_of(p) + 0.01 * warm, {
            "fork_state": {"warm": warm + 1}, "cost_seconds": 0.001}


# ---------------------------------------------------------------------------
# the seam: base class + lifecycle
# ---------------------------------------------------------------------------

def test_rungscheduler_import_paths_are_one_class():
    """Every scheduler is a ``TrialScheduler`` behind the one seam."""
    assert issubclass(RungScheduler, TrialScheduler)
    assert issubclass(HyperBandScheduler, TrialScheduler)
    assert issubclass(PBTScheduler, TrialScheduler)


def test_build_scheduler_maps_kinds():
    mf = MultiFidelityConfig(enabled=True, min_fidelity=1 / 9)
    assert isinstance(build_scheduler(mf), RungScheduler)
    mf.scheduler = "hyperband"
    assert isinstance(build_scheduler(mf), HyperBandScheduler)
    mf.scheduler = "pbt"
    assert isinstance(build_scheduler(mf, space=make_space()), PBTScheduler)
    with pytest.raises(ValueError, match="search space"):
        build_scheduler(mf)
    mf.scheduler = "sobol"
    with pytest.raises(ValueError, match="sobol"):
        build_scheduler(mf, space=make_space())


# ---------------------------------------------------------------------------
# HyperBand: bracket shapes, budget split, replay routing
# ---------------------------------------------------------------------------

def test_hyperband_bracket_shapes_and_offsets():
    """min_fidelity=1/9, eta=3: deepest ladder 1/9 -> 1/3 -> 1, then the
    staggered shallower brackets 1/3 -> 1 and the full-fidelity-only
    one.  Global rung ids are bracket offsets + inner rungs."""
    hb = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9)
    assert [b.n_rungs for b in hb.brackets] == [3, 2, 1]
    assert hb._offsets == [0, 3, 5]
    assert [round(b.base_fidelity, 6) for b in hb.brackets] \
        == [round(1 / 9, 6), round(1 / 3, 6), 1.0]
    # the brackets cap keeps the deepest ladders
    hb2 = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9, brackets=2)
    assert [b.n_rungs for b in hb2.brackets] == [3, 2]
    with pytest.raises(ValueError, match="brackets"):
        HyperBandScheduler(eta=3.0, min_fidelity=1 / 9, brackets=9)


def test_hyperband_admits_to_least_spent_bracket():
    hb = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9)
    acts = [hb.admit((i,), {"x": i}) for i in range(4)]
    # bracket 0 is cheapest per admission, so it absorbs several fresh
    # candidates before its cumulative spend passes bracket 1's
    assert acts[0].lineage == "b0"
    lineages = {a.lineage for a in acts}
    assert len(lineages) >= 2  # the split spreads across brackets
    # every admission entered its bracket's bottom rung at that fidelity
    for a in acts:
        i = int(a.lineage[1:])
        assert a.rung == hb._offsets[i]
        assert a.fidelity == pytest.approx(hb.brackets[i].base_fidelity)


def test_hyperband_spend_trueup_and_preempt_refund():
    hb = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9, brackets=1)
    act = hb.admit(("a",), {"x": 0})
    assert hb._spend[0] == pytest.approx(1 / 9)  # planned at dispatch
    hb.on_started(("a",), {"x": 0}, act.rung, lineage=act.lineage)
    # delivered more than planned (executor upgraded the request)
    hb.on_result(("a",), {"x": 0}, 5.0, act.rung, fidelity=1 / 3,
                 lineage=act.lineage)
    assert hb._spend[0] == pytest.approx(1 / 3)  # trued up
    # a cancelled preemption refunds the planned spend
    before = hb._spend[0]
    act2 = hb.admit(("b",), {"x": 1})
    hb.on_preempted(("b",), act2.rung, lineage=act2.lineage)
    assert hb._spend[0] == pytest.approx(before)


def test_hyperband_replay_routes_by_lineage_and_matches_live():
    """A crashed-and-replayed HyperBand equals the never-crashed one:
    same per-bracket results, promotion marks, and spend."""
    def feed(hb):
        recs = []
        for i in range(6):
            act = hb.admit((i,), {"x": i})
            hb.on_started((i,), {"x": i}, act.rung, lineage=act.lineage)
            hb.on_result((i,), {"x": i}, float(i), act.rung,
                         fidelity=act.fidelity, lineage=act.lineage)
            recs.append(((i,), {"x": i}, float(i), act.fidelity, act.rung,
                         act.lineage))
        return recs

    live = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9)
    recs = feed(live)

    resumed = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9)
    charged = sum(resumed.replay(k, p, v, f, rung=r, lineage=lin)
                  for k, p, v, f, r, lin in recs)
    assert charged == pytest.approx(sum(f for *_, f, _r, _l in recs))

    def state(hb):
        return [(sorted(map(repr, b.rungs[r].results)),
                 sorted(map(repr, b.rungs[r].promoted)))
                for b in hb.brackets for r in range(b.n_rungs)]
    assert state(resumed) == state(live)
    assert resumed._spend == pytest.approx(live._spend)
    # duplicates and preempted placeholders charge nothing
    k, p, v, f, r, lin = recs[0]
    assert resumed.replay(k, p, v, f, rung=r, lineage=lin) == 0.0
    assert resumed.replay(("z",), {"x": 9}, 1.0, 1.0, rung=0, lineage="b0",
                          meta={"preempted": True}) == 0.0


def test_hyperband_stats_rows_carry_bracket_and_global_rung():
    hb = HyperBandScheduler(eta=3.0, min_fidelity=1 / 9)
    rows = hb.stats()
    assert [r["rung"] for r in rows] == list(range(6))
    assert [r["bracket"] for r in rows] == [0, 0, 0, 1, 1, 2]
    snap = hb.snapshot()
    json.dumps(snap)  # wire-safe for job_status
    assert [b["bracket"] for b in snap["brackets"]] == [0, 1, 2]


# ---------------------------------------------------------------------------
# PBT: population, forks, doom, replay
# ---------------------------------------------------------------------------

def _pbt(population=4, **kw):
    kw.setdefault("exploit_quantile", 0.25)
    kw.setdefault("step_fidelity", 0.5)
    return PBTScheduler(make_space(), population=population, seed=3, **kw)


def _seed_population(s, n):
    """Admit n members and give each a first-step value."""
    for i in range(n):
        point = {"inter_op": 1 + i % 4, "intra_op": 10 * (i % 4), "build": 1}
        act = s.admit((i,), point)
        assert act is not None and act.lineage == f"m{i}"
        s.on_started((i,), point, act.rung, lineage=act.lineage)
        s.on_result((i,), point, float(i), act.rung, fidelity=act.fidelity,
                    lineage=act.lineage)


def test_pbt_admission_caps_at_population():
    s = _pbt(population=3)
    assert s.fresh_quota(10) == 3
    _seed_population(s, 3)
    assert s.fresh_quota(10) == 0
    assert s.admit((9,), {"inter_op": 1, "intra_op": 0, "build": 1}) is None


def test_pbt_under_population_defers_then_steps():
    """While under-populated, next_action yields to fresh admission —
    but only until a driver cycle passes with no admission (dry engine),
    then it steps the members it has rather than stall."""
    s = _pbt(population=4)
    _seed_population(s, 2)
    assert s.next_action() is None      # defer: let the driver admit
    act = s.next_action()               # no admit happened: step anyway
    assert act is not None and act.kind == "step"


def test_pbt_bottom_member_is_replaced_by_fork():
    s = _pbt(population=4)
    _seed_population(s, 4)
    act = s.next_action()
    # the bottom-quantile member (value 0.0) is culled; the replacement
    # clones a top-quantile donor's point (perturbed) and checkpoint
    assert act.kind == "fork"
    assert act.lineage == "m4"
    assert "m0" not in s._members
    assert s.n_forks == 1
    child = s._members["m4"]
    assert child.parent in {"m2", "m3"}


def test_pbt_fork_carries_donor_checkpoint():
    s = _pbt(population=4)
    _seed_population(s, 4)
    for m in s._members.values():
        m.state = {"warm": int(m.value) + 1}
    act = s.next_action()
    assert act.kind == "fork"
    donor = s._members[act.lineage].parent
    assert act.state == {"warm": {"m2": 3, "m3": 4}[donor]}


def _doom_running_m1(s):
    """Drive the scheduler into the race setup: m1's step is in flight
    when a completion re-ranks it into the bottom quantile (doomed)."""
    fork = s.next_action()            # m0 (bottom) replaced by fork m4
    assert fork.kind == "fork" and fork.lineage == "m4"
    s.on_started(None, fork.point, fork.rung, lineage=fork.lineage)
    step = s.next_action()            # m4 unvalued -> no cull: plain step
    assert step.kind == "step" and step.lineage == "m1"
    s.on_started(None, step.point, step.rung, lineage=step.lineage)
    # the fork's completion makes the population fully valued with m1
    # (value 1.0, still running) now in the bottom quantile: doomed
    s.on_result(None, fork.point, 50.0, fork.rung, lineage=fork.lineage)
    assert s._members["m1"].doomed
    assert s.decide(None, step.rung, lineage="m1") == "preempt"
    return step


def test_pbt_doomed_running_member_forks_exactly_once_via_result():
    """The preemption race, completion-wins arm: decide() says preempt,
    the executor reports the step already done, so the driver records it
    and calls on_result — which must fork exactly once (and
    on_preempted must NOT also fire)."""
    s = _pbt(population=4)
    _seed_population(s, 4)
    step = _doom_running_m1(s)
    forks_before = s.n_forks
    # completion won the race: the driver consumes the result normally
    s.on_result(None, step.point, 0.5, step.rung, lineage="m1")
    assert s.n_forks == forks_before + 1
    assert "m1" not in s._members
    # the doom mark was consumed: nothing left to preempt
    assert s.decide(None, step.rung, lineage="m1") == "continue"


def test_pbt_doomed_cancelled_member_forks_exactly_once_via_preempt():
    """The other arm: the preempt lands as cancelled, on_preempted forks
    the replacement, and there is no completion to double-fork on."""
    s = _pbt(population=4)
    _seed_population(s, 4)
    step = _doom_running_m1(s)
    forks_before = s.n_forks
    s.on_preempted(None, step.rung, lineage="m1")
    assert s.n_forks == forks_before + 1
    assert s.n_preempted == 1
    assert "m1" not in s._members


def test_pbt_replay_rebuilds_population_latest_step_wins():
    s = _pbt(population=4)
    s.replay((0,), {"inter_op": 1, "intra_op": 0, "build": 1}, 1.0, 0.5,
             rung=0, lineage="m0", meta={"fork_state": {"warm": 1}})
    s.replay((0,), {"inter_op": 2, "intra_op": 0, "build": 1}, 2.0, 0.5,
             rung=1, lineage="m0", meta={"fork_state": {"warm": 2}})
    # a duplicate of (m0, step 1) — the checkpoint-race artifact — and a
    # preempted placeholder both charge nothing
    assert s.replay((0,), {"inter_op": 2, "intra_op": 0, "build": 1}, 2.0,
                    0.5, rung=1, lineage="m0") == 0.0
    assert s.replay((1,), {"inter_op": 3, "intra_op": 0, "build": 1}, 3.0,
                    0.5, rung=0, lineage="m7",
                    meta={"preempted": True}) == 0.0
    m = s._members["m0"]
    assert (m.steps, m.value, m.point["inter_op"]) == (2, 2.0, 2)
    assert m.state == {"warm": 2}
    # lineage counter resumes past the replayed names: no collisions
    assert s._n_lineages >= 1
    act = s.admit((5,), {"inter_op": 4, "intra_op": 0, "build": 2})
    assert act.lineage not in {"m0", "m7"}


def test_pbt_snapshot_is_jsonable_and_names_lineage():
    s = _pbt(population=4)
    _seed_population(s, 4)
    snap = s.snapshot()
    json.dumps(snap)
    assert snap["population"] == 4
    assert [m["lineage"] for m in snap["members"]] \
        == [m.lineage for m in s._members.values()]
    row = s.stats()[0]
    assert row["members"] == 4 and row["best"] == 3.0


# ---------------------------------------------------------------------------
# the driver: all three schedulers end-to-end, exactly-once under races
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["asha", "hyperband", "pbt"])
def test_driver_runs_scheduler_exactly_once(kind, tmp_path):
    """Every scheduler through the same driver, with preemption on and
    enough parallelism that decide()-preempts race completions: every
    (lineage-or-key, rung) is recorded at most once, spend covers the
    budget, and PBT provenance lands in history."""
    obj = ForkCapable()
    mf = MultiFidelityConfig(enabled=True, scheduler=kind,
                             min_fidelity=1 / 9, eta=3.0, preempt=True)
    mf.pbt.population = 4
    mf.pbt.step_fidelity = 0.5
    t = Tuner(obj, make_space(), TunerConfig(
        algorithm="random", budget=12, seed=5, verbose=False,
        executor=ExecutorConfig(parallelism=4),
        checkpoint_path=str(tmp_path / "ckpt.json"), multi_fidelity=mf))
    h = t.run()
    t.close()
    assert len(h) > 0
    # trial identity: PBT's is its lineage+step, the ladders' is
    # (point, rung) — lineage there is the bracket tag, shared
    keys = [(e.lineage, t.space.key(e.point), e.rung)
            for e in h.evals if not e.meta.get("preempted")]
    assert len(keys) == len(set(keys))
    # spend never exceeds budget by more than the in-flight overhang
    # (it may fall short: the finite space can exhaust the engine first)
    spend = sum(e.fidelity for e in h.evals)
    assert 0 < spend <= 12 + 4
    if kind == "pbt":
        assert all(e.lineage for e in h.evals)
        assert any(e.meta.get("fork_state") for e in h.evals)
        # forked lineages name their parent in provenance
        snap = t.rung_scheduler.snapshot()
        assert any(m["parent"] for m in snap["members"]) \
            or t.rung_scheduler.n_forks == 0


@pytest.mark.parametrize("kind", ["asha", "hyperband", "pbt"])
def test_driver_resume_replays_scheduler_state(kind, tmp_path):
    """Crash after a short run, resume with a larger budget: nothing the
    checkpoint holds is re-measured at the same (lineage/key, rung), and
    the resumed scheduler starts from the replayed state."""
    def mk(budget):
        mf = MultiFidelityConfig(enabled=True, scheduler=kind,
                                 min_fidelity=1 / 9, eta=3.0)
        mf.pbt.population = 4
        mf.pbt.step_fidelity = 0.5
        return TunerConfig(algorithm="random", budget=budget, seed=5,
                           verbose=False,
                           executor=ExecutorConfig(parallelism=2),
                           checkpoint_path=str(tmp_path / "ckpt.json"),
                           multi_fidelity=mf)

    t1 = Tuner(ForkCapable(), make_space(), mk(4))
    h1 = t1.run()
    t1.close()
    assert len(h1) > 0

    obj2 = ForkCapable()
    t2 = Tuner(obj2, make_space(), mk(9))
    assert len(t2.history) == len(h1)  # the whole checkpoint replayed
    h2 = t2.run()
    t2.close()
    keys = [(e.lineage, t2.space.key(e.point), e.rung)
            for e in h2.evals if not e.meta.get("preempted")]
    assert len(keys) == len(set(keys))
    assert len(h2) > len(h1)
    if kind == "pbt":
        # replayed lineages kept their step counters: new steps continue
        # past the checkpoint instead of restarting at 0
        by_lineage = {}
        for e in h2.evals:
            by_lineage.setdefault(e.lineage, []).append(e.rung)
        assert any(max(rungs) >= 1 for rungs in by_lineage.values())


class SlowForkCapable(ForkCapable):
    """ForkCapable with a measurable step, so a worker can die mid-run."""

    def __call__(self, p, fidelity=None, resume_state=None):
        time.sleep(0.02)
        return super().__call__(p, fidelity, resume_state)


def test_pbt_fork_state_survives_worker_death():
    """PBT over two in-process workers, one of which dies mid-run: its
    in-flight steps, checkpoint-fork ``state`` blobs included, are
    reinjected onto the survivor, and the history holds no duplicate
    (lineage, step) record.  The run has 8 s of its own to finish."""
    objs = [SlowForkCapable(), SlowForkCapable()]
    servers = [WorkerServer(o, slots=2, heartbeat_s=0.1).start()
               for o in objs]
    mf = MultiFidelityConfig(enabled=True, scheduler="pbt",
                             min_fidelity=0.5)
    mf.pbt.population = 4
    tuner = Tuner(objs[0], make_space(), TunerConfig(
        algorithm="random", budget=15, seed=11, verbose=False,
        executor=ExecutorConfig(workers=[s.address for s in servers]),
        multi_fidelity=mf))
    done, errors = threading.Event(), []

    def run():
        try:
            tuner.run()
        except Exception as e:  # surfaced by the assertion below
            errors.append(e)
        finally:
            done.set()

    threading.Thread(target=run, daemon=True).start()
    deadline = time.time() + 8.0
    while len(tuner.history) < 8 and time.time() < deadline:
        time.sleep(0.01)
    killed_at = len(tuner.history)
    servers[1].stop()  # a measurement host dies with steps in flight
    finished = done.wait(timeout=max(0.0, deadline - time.time()))
    try:
        assert finished, "the run did not finish after the worker died"
        assert not errors, errors
        assert 0 < killed_at < len(tuner.history)
        pairs = [(e.lineage, e.rung) for e in tuner.history.evals]
        assert len(pairs) == len(set(pairs))  # 0 duplicate steps
        assert tuner.rung_scheduler.n_forks >= 1
        # each step resumed from its lineage's previous state blob, a
        # reinjected one included: the warm counter climbs by one a step
        warms = {}
        for e in sorted(tuner.history.evals, key=lambda e: e.rung):
            warms.setdefault(e.lineage, []).append(
                e.meta["fork_state"]["warm"])
        assert any(len(w) > 1 for w in warms.values())
        for w in warms.values():
            assert all(b == a + 1 for a, b in zip(w, w[1:])), w
    finally:
        tuner.close()
        servers[0].stop()
