"""Checkpoint round-trip fault tolerance (the tuning service's crash
contract): a Tuner snapshotted mid-run and restored must equal a
never-crashed run minus only the evaluations that were in flight at the
kill — nothing recorded is lost, nothing is double-measured, and the
multi-fidelity rung scheduler's replayed state keeps promoted survivors
promoted.
"""
import json
import math
import pathlib

import pytest

from repro.checkpoint.checkpointer import JsonCheckpointer
from repro.core import (CatDim, ExecutorConfig, History, IntDim,
                        MultiFidelityConfig, Observation, SearchSpace, Tuner,
                        TunerConfig)
from repro.tuning.objective import CountingEvaluator, Evaluator
from repro.tuning.schedulers import RungScheduler


def make_space() -> SearchSpace:
    return SearchSpace([IntDim("inter_op", 1, 4),
                        IntDim("intra_op", 0, 30, 10),
                        CatDim("build", (1, 2))])


def value_of(p):
    return float(3.0 * p["inter_op"] + 0.2 * p["intra_op"] + 7.0 * p["build"])


class FidelityObjective(Evaluator):
    supports_fidelity = True

    def __init__(self):
        self.calls = []  # (key, fidelity) per real measurement

    def __call__(self, p, fidelity=None):
        f = 1.0 if fidelity is None else float(fidelity)
        self.calls.append(((p["inter_op"], p["intra_op"], p["build"]), f))
        wiggle = ((p["inter_op"] * 13 + p["intra_op"] * 7) % 5 - 2) / 3.0
        return value_of(p) + (1.0 - f) * wiggle, {"cost_seconds": 0.01 * f}


def cfg(tmp_path, **kw):
    kw.setdefault("algorithm", "exhaustive")
    kw.setdefault("verbose", False)
    kw.setdefault("checkpoint_path", str(tmp_path / "ckpt.json"))
    return TunerConfig(**kw)


# ---------------------------------------------------------------------------
# Tuner resume equality
# ---------------------------------------------------------------------------

def test_resume_equals_uninterrupted_run(tmp_path):
    """Crash after k evals + resume == one uninterrupted run (exhaustive
    engine: fully determined by history, so equality is exact)."""
    space = make_space()
    budget = 12

    straight = Tuner(value_of, space,
                     cfg(tmp_path / "a", budget=budget)).run()

    # crashed run: stop at k, then a NEW tuner resumes from the checkpoint
    k = 5
    Tuner(value_of, space, cfg(tmp_path / "b", budget=k)).run()
    resumed = Tuner(value_of, space,
                    cfg(tmp_path / "b", budget=budget)).run()

    assert [(e.point, e.value) for e in resumed.evals] \
        == [(e.point, e.value) for e in straight.evals]


def test_resume_measures_only_the_lost_suffix(tmp_path):
    """A resumed run re-measures nothing the checkpoint already holds."""
    space = make_space()
    Tuner(value_of, space, cfg(tmp_path, budget=6)).run()
    prefix = {tuple(sorted(e.point.items()))
              for e in History.load(tmp_path / "ckpt.json", space).evals}

    counting = CountingEvaluator(value_of)
    resumed = Tuner(counting, space, cfg(tmp_path, budget=10)).run()
    assert len(resumed) == 10
    assert counting.calls == 10 - len(prefix)


def test_resume_drops_only_inflight(tmp_path):
    """Simulated SIGKILL mid-measurement: the checkpoint holds completed
    evaluations only, so a resumed run loses exactly the in-flight one
    (it is re-measured, not double-recorded)."""
    space = make_space()
    Tuner(value_of, space, cfg(tmp_path, budget=7)).run()
    path = tmp_path / "ckpt.json"
    evals = json.loads(path.read_text())
    lost = evals.pop()  # the in-flight eval a crash would not have saved
    path.write_text(json.dumps(evals))

    counting = CountingEvaluator(value_of)
    resumed = Tuner(counting, space, cfg(tmp_path, budget=7)).run()
    assert len(resumed) == 7
    # the lost point was measured again, and nothing else was
    assert counting.calls == 1
    measured = [e.point for e in resumed.evals if not e.meta.get("memoized")]
    assert lost["point"] in measured
    # no point appears twice in the resumed record
    keys = [space.key(e.point) for e in resumed.evals]
    assert len(keys) == len(set(keys))


def test_resume_replays_through_tell_as_observations(tmp_path):
    """The resume path feeds the engine Observation records (the v2 tell
    API) — fidelities and costs survive the round trip."""
    space = make_space()
    h = History(space)
    h.add_observations([
        Observation(point={"inter_op": 1, "intra_op": 0, "build": 1},
                    value=1.5, cost_seconds=0.25, fidelity=0.5),
        Observation(point={"inter_op": 2, "intra_op": 10, "build": 2},
                    value=3.0, cost_seconds=1.0),
    ])
    path = tmp_path / "h.json"
    h.save(path)
    loaded = History.load(path, space)
    obs = loaded.observations()
    assert [(o.value, o.cost_seconds, o.fidelity) for o in obs] \
        == [(1.5, 0.25, 0.5), (3.0, 1.0, 1.0)]


# ---------------------------------------------------------------------------
# multi-fidelity: rung state restore
# ---------------------------------------------------------------------------

def _rung_state(sched):
    """Comparable rung state: results + promotion marks (not counters —
    replay deliberately leaves this-run scheduling counters at zero)."""
    return [(sorted(r.results), sorted(r.promoted)) for r in sched.rungs]


def test_rungscheduler_replay_reconstructs_state():
    a = RungScheduler(eta=2.0, min_fidelity=0.25)
    pts = {k: {"x": i} for i, k in enumerate("abcd")}
    for k, v in [("a", 10.0), ("b", 4.0), ("c", 8.0), ("d", 1.0)]:
        a.on_result((k,), pts[k], v, 0)
    promo = a.next_promotion()
    assert promo is not None
    point, rung = promo
    a.on_result(("a",), point, 10.5, rung)

    # replay from the trace a checkpoint would hold (key, point, value,
    # fidelity) — completion order, fidelities as recorded
    b = RungScheduler(eta=2.0, min_fidelity=0.25)
    for k, v in [("a", 10.0), ("b", 4.0), ("c", 8.0), ("d", 1.0)]:
        b.replay((k,), pts[k], v, a.fidelity(0))
    b.replay(("a",), pts["a"], 10.5, a.fidelity(rung))

    assert _rung_state(a) == _rung_state(b)
    # the replayed survivor stays promoted: it must NOT be promotable again
    nxt = b.next_promotion()
    assert nxt is None or b.rungs[nxt[1] - 1].promoted != {("a",)}


def test_rungscheduler_replay_marks_source_rung_promoted():
    """A rung-r result replays as promoted-out-of-rung-(r-1); without the
    mark a resumed run would re-promote (and re-measure) it."""
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    s.replay(("k",), {"x": 1}, 5.0, s.fidelity(1))
    assert ("k",) in s.rungs[0].promoted
    assert s.rungs[1].results == [(("k",), 5.0)]


def test_rungscheduler_replay_dedupes_preemption_race_records():
    """Regression: a checkpoint written around a preemption race can
    hold BOTH a preempted placeholder and a completed record for the
    same (key, rung).  Replay used to charge budget for both and rank
    the key twice; now the preempted record charges 0 and skips, and a
    duplicate completion charges 0 and is not re-ranked."""
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    f0 = s.fidelity(0)
    # the preempted placeholder measured nothing: no charge, no state
    assert s.replay(("k",), {"x": 1}, 0.0, f0,
                    meta={"preempted": True}) == 0.0
    assert s.rungs[0].results == []
    # the completed record charges once...
    assert s.replay(("k",), {"x": 1}, 5.0, f0) == pytest.approx(f0)
    # ...and its duplicate (same key, same rung) charges nothing
    assert s.replay(("k",), {"x": 1}, 5.0, f0) == 0.0
    assert s.rungs[0].results == [(("k",), 5.0)]
    assert s.rungs[0].n_completed == 1


def test_rungscheduler_replay_dedupe_is_per_rung_not_per_key():
    """The same key legitimately completes once per rung of the ladder;
    only same-rung duplicates are checkpoint artifacts."""
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    charged = [s.replay(("k",), {"x": 1}, 5.0, s.fidelity(r))
               for r in range(s.n_rungs)]
    assert charged == pytest.approx([s.fidelity(r)
                                     for r in range(s.n_rungs)])
    assert [r.n_completed for r in s.rungs] == [1] * s.n_rungs


def test_rungscheduler_snapshot_is_jsonable_and_complete():
    s = RungScheduler(eta=3.0, min_fidelity=0.1)
    s.on_started(("a", 1), {"x": 0, "y": 1}, 0)
    s.on_result(("a", 1), {"x": 0, "y": 1}, 2.0, 0)
    snap = s.snapshot()
    json.dumps(snap)  # wire-safe
    assert snap[0]["completed"] == 1
    assert snap[0]["results"] == [[["a", 1], 2.0]]


def test_multi_fidelity_resume_no_remeasure_and_spend_carries(tmp_path):
    """Resuming a multi-fidelity run replays rung state AND budget spend:
    checkpointed (point, fidelity) completions are never measured again,
    and the resumed run finishes the remaining budget only."""
    space = make_space()

    def mf_cfg(budget):
        return cfg(tmp_path, algorithm="random", budget=budget,
                   multi_fidelity=MultiFidelityConfig(
                       enabled=True, eta=2.0, min_fidelity=0.5),
                   executor=ExecutorConfig(parallelism=2))

    first = FidelityObjective()
    t1 = Tuner(first, space, mf_cfg(budget=4))
    h1 = t1.run()
    t1.close()
    assert len(h1) > 0
    spend1 = sum(e.fidelity for e in h1.evals)

    second = FidelityObjective()
    t2 = Tuner(second, space, mf_cfg(budget=8))
    pre = len(t2.history)
    assert pre == len(h1)  # the whole checkpoint replayed
    h2 = t2.run()
    t2.close()

    # nothing the checkpoint already held was re-measured
    replayed = {(space.key(e.point), round(e.fidelity, 9))
                for e in h1.evals}
    remeasured = [c for c in second.calls
                  if (space.key({"inter_op": c[0][0], "intra_op": c[0][1],
                                 "build": c[0][2]}), round(c[1], 9))
                  in replayed]
    assert remeasured == []
    # budget accounting resumed, not restarted: total spend covers the
    # full budget but the new run paid only the difference
    spend2 = sum(e.fidelity for e in h2.evals)
    assert spend2 >= 8.0 - 1.0  # reached (within one final grant)
    new_spend = sum(e.fidelity for e in h2.evals[pre:])
    assert new_spend == pytest.approx(spend2 - spend1)


# ---------------------------------------------------------------------------
# JsonCheckpointer (the service's job-document store)
# ---------------------------------------------------------------------------

def test_json_checkpointer_roundtrip_and_retention(tmp_path):
    c = JsonCheckpointer(tmp_path, keep_last=2)
    for i in range(5):
        c.save({"i": i})
    assert c.load() == {"i": 4}
    assert len(list(pathlib.Path(tmp_path).glob("snap_*.json"))) == 2


def test_json_checkpointer_survives_torn_write(tmp_path):
    c = JsonCheckpointer(tmp_path, keep_last=3)
    c.save({"i": 0})
    c.save({"i": 1})
    snaps = sorted(pathlib.Path(tmp_path).glob("snap_*.json"))
    snaps[-1].write_text(snaps[-1].read_text()[:-25])  # the crash tore it
    assert c.load() == {"i": 0}


def test_json_checkpointer_empty_dir_loads_none(tmp_path):
    assert JsonCheckpointer(tmp_path).load() is None


# ---------------------------------------------------------------------------
# cooperative stop (the service's cancel_job path)
# ---------------------------------------------------------------------------

def test_request_stop_preserves_recorded_history(tmp_path):
    space = make_space()
    tuner = Tuner(value_of, space, cfg(tmp_path, budget=1000))
    tuner.request_stop()  # before run: exits at the first loop check
    h = tuner.run()
    assert len(h) == 0 or len(h) < 1000
    assert math.isfinite(sum(e.value for e in h.evals) + 0.0)
    # the stop is resumable: a fresh tuner picks the checkpoint up
    again = Tuner(value_of, space, cfg(tmp_path, budget=5)).run()
    assert len(again) == 5
