"""Elastic measurement fleets: mid-run worker join/leave over the
pool's always-open join socket, speculative straggler re-execution with
exactly-once recording, hardware-fingerprint partitioning (strict vs
normalize homogeneity), per-worker heartbeat stall windows, and the
multi-fidelity drain surviving a mid-drain worker kill."""
import socket
import threading
import time

import pytest

from repro.core import ExecutorConfig, IntDim, SearchSpace, Tuner, TunerConfig
from repro.tuning.corpus import TuningCorpus
from repro.tuning.executor import EvaluationExecutor
from repro.tuning.fidelity import CompletionStats, StreamingQuantiles
from repro.tuning.objective import Evaluator
from repro.tuning.remote import (
    UNKNOWN_FINGERPRINT,
    UNKNOWN_PARTITION,
    FleetOptions,
    RemoteWorkerPool,
    WorkerServer,
    fingerprint_id,
    recv_msg,
    send_msg,
)


def small_space() -> SearchSpace:
    return SearchSpace([IntDim("a", 0, 20), IntDim("b", 0, 9)])


def value_of(p) -> float:
    return float(p["a"] * 10 + p["b"])


def local(pool: RemoteWorkerPool) -> str:
    """The pool's join address, dialable from this host."""
    port = pool.join_address.rsplit(":", 1)[1]
    return f"127.0.0.1:{port}"


class GatedObjective(Evaluator):
    """Deterministic value; selected points block on an event (one
    instance per in-process worker, so a gate stalls exactly one host)."""

    def __init__(self):
        self.gates = {}
        self.calls = []
        self._lock = threading.Lock()

    def gate(self, a, b) -> threading.Event:
        ev = threading.Event()
        self.gates[(a, b)] = ev
        return ev

    def __call__(self, p, fidelity=None):
        key = (p["a"], p["b"])
        with self._lock:
            self.calls.append(key)
        ev = self.gates.get(key)
        if ev is not None:
            assert ev.wait(20.0), f"test gate for {key} never released"
        # declared cost: deterministic, independent of which worker ran it
        return value_of(p), {"src": "worker", "cost_seconds": 0.01}


def wait_until(cond, timeout=5.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# streaming quantiles / completion stats (tuning/fidelity.py)
# ---------------------------------------------------------------------------

def test_streaming_quantiles_nearest_rank():
    q = StreamingQuantiles()
    for v in [5.0, 1.0, 3.0, 2.0, 4.0]:
        q.add(v)
    assert q.n == 5
    assert q.p50() == 3.0
    assert q.p95() == 5.0
    assert q.quantile(0.0) == 1.0


def test_streaming_quantiles_ignores_garbage_and_caps_window():
    q = StreamingQuantiles(max_samples=8)
    q.add(float("nan"))
    q.add(float("inf"))
    q.add(-1.0)
    assert q.n == 0 and q.p95() is None
    for v in [100.0, 200.0] + [float(i) for i in range(1, 9)]:
        q.add(v)
    # ring buffer: the samples from a departed slow host age out, so
    # only the 8 most recent observations shape the quantiles
    assert q.n == 10
    assert q.p95() == 8.0


def test_completion_stats_buckets_by_fidelity():
    cs = CompletionStats()
    for s in [1.0, 2.0, 3.0]:
        cs.record(None, s)       # None keys as full fidelity
    cs.record(1.0, 4.0)          # ... the same bucket as None
    cs.record(0.33, 10.0)
    assert cs.observations(None) == 4
    assert cs.observations(1.0) == 4
    assert cs.observations(0.33) == 1
    assert cs.p95(None) == 4.0
    assert cs.p95(0.33) == 10.0
    assert cs.p95(0.5) is None   # never-observed rung: no threshold
    snap = {row["fidelity"]: row for row in cs.snapshot()}
    assert snap[1.0]["n"] == 4 and snap[0.33]["n"] == 1


def test_fingerprint_id_is_stable_and_order_insensitive():
    a = fingerprint_id({"backend": "cpu", "cores": 8})
    b = fingerprint_id({"cores": 8, "backend": "cpu"})
    assert a == b and len(a) == 12
    assert fingerprint_id(None) == fingerprint_id(UNKNOWN_FINGERPRINT)
    assert fingerprint_id(UNKNOWN_FINGERPRINT) == UNKNOWN_PARTITION
    assert fingerprint_id({"backend": "gpu"}) != a


# ---------------------------------------------------------------------------
# mid-run join / clean leave
# ---------------------------------------------------------------------------

def test_mid_run_join_grows_live_parallelism():
    obj1, obj2 = GatedObjective(), GatedObjective()
    s1 = WorkerServer(obj1, slots=1, heartbeat_s=0.1).start()
    ex = EvaluationExecutor(obj1, small_space(), workers=[s1.address],
                            fleet=FleetOptions(speculation=False))
    assert ex.parallelism == 1
    s2 = WorkerServer(obj2, slots=2, heartbeat_s=0.1)
    s2.start_join(local(ex.remote_pool))
    wait_until(lambda: ex.parallelism == 3, msg="joiner registering")
    pts = [{"a": i, "b": 0} for i in range(6)]
    results = [p.result() for p in ex.as_completed(ex.submit(pts))]
    assert sorted(r.value for r in results) == sorted(
        value_of(p) for p in pts)
    # the joiner actually measured (capacity was real, not cosmetic)
    assert obj2.calls
    rows = {w["address"]: w for w in ex.remote_pool.fleet_health()}
    joined = [w for w in rows.values() if w["origin"] == "join"]
    assert len(joined) == 1 and joined[0]["slots"] == 2
    for w in rows.values():  # elastic health fields are always present
        assert isinstance(w["fingerprint"], dict)
        assert w["partition"] and w["joined_at"] > 0
        assert "inflight_age_max" in w and "speculating" in w
    ex.close()
    s1.stop()
    s2.stop()


def test_empty_elastic_start_queues_until_first_join():
    obj = GatedObjective()
    ex = EvaluationExecutor(obj, small_space(), backend="remote",
                            fleet=FleetOptions(speculation=False))
    assert ex.remote_pool.join_address is not None
    pend = ex.submit([{"a": 4, "b": 2}])  # queues: no worker yet
    w = WorkerServer(obj, slots=1, heartbeat_s=0.1)
    w.start_join(local(ex.remote_pool))
    done = ex.next_completed(pend)
    assert done.result().value == value_of(done.point)
    ex.close()
    w.stop()


def test_remote_without_workers_or_join_socket_still_fails():
    with pytest.raises(ValueError, match="backend='remote'"):
        EvaluationExecutor(GatedObjective(), small_space(), backend="remote",
                           fleet=FleetOptions(listen_port=None))


def test_clean_leave_drains_inflight_and_shrinks_capacity():
    obj1, obj2 = GatedObjective(), GatedObjective()
    hold = obj1.gate(9, 9)
    s1 = WorkerServer(obj1, slots=1, heartbeat_s=0.1).start()
    s2 = WorkerServer(obj2, slots=1, heartbeat_s=0.1).start()
    ex = EvaluationExecutor(obj1, small_space(),
                            workers=[s1.address, s2.address],
                            fleet=FleetOptions(speculation=False))
    pool = ex.remote_pool
    (pend,) = ex.submit([{"a": 9, "b": 9}])  # dispatches to s1 (first free)
    wait_until(lambda: (9, 9) in obj1.calls, msg="dispatch to s1")
    assert s1.request_leave()
    # draining: capacity excludes the leaver immediately, but its
    # in-flight measurement is NOT abandoned
    wait_until(lambda: ex.parallelism == 1, msg="drain to start")
    assert not pend.done()
    hold.set()
    done = ex.next_completed([pend])
    assert done.result().value == value_of({"a": 9, "b": 9})
    wait_until(lambda: pool.clean_leaves == 1, msg="clean leave")
    assert pool.alive_workers() == 1
    assert obj1.calls == [(9, 9)] and obj2.calls == []  # measured once
    # the remaining worker keeps serving new work
    (p2,) = ex.submit([{"a": 1, "b": 1}])
    assert ex.next_completed([p2]).result().value == 11.0
    ex.close()
    s1.stop()
    s2.stop()


def test_leave_with_empty_inflight_departs_immediately():
    obj = GatedObjective()
    s1 = WorkerServer(obj, slots=1, heartbeat_s=0.1).start()
    s2 = WorkerServer(obj, slots=1, heartbeat_s=0.1).start()
    ex = EvaluationExecutor(obj, small_space(),
                            workers=[s1.address, s2.address],
                            fleet=FleetOptions(speculation=False))
    pool = ex.remote_pool
    assert s2.request_leave()
    wait_until(lambda: pool.clean_leaves == 1, msg="idle leave")
    assert ex.parallelism == 1
    ex.close()
    s1.stop()
    s2.stop()


# ---------------------------------------------------------------------------
# speculative straggler re-execution: exactly-once under both orderings
# ---------------------------------------------------------------------------

def _speculation_fleet(tmp_path):
    """(slow_obj, fast_obj, s_slow, s_fast, executor, corpus): 1-slot
    straggler + 1-slot healthy worker with aggressive speculation."""
    slow_obj, fast_obj = GatedObjective(), GatedObjective()
    s_slow = WorkerServer(slow_obj, slots=1, heartbeat_s=0.1).start()
    s_fast = WorkerServer(fast_obj, slots=1, heartbeat_s=0.1).start()
    corpus = TuningCorpus(tmp_path / "corpus.json", job_id="spec")
    ex = EvaluationExecutor(
        slow_obj, small_space(),
        workers=[s_slow.address, s_fast.address],
        cache_path=str(tmp_path / "memo.json"), corpus=corpus,
        fleet=FleetOptions(speculation=True, speculation_factor=2.0,
                           min_observations=3))
    return slow_obj, fast_obj, s_slow, s_fast, ex, corpus


def _warmup(ex, n=4):
    """Seed the completion stats so the p95 threshold is trusted."""
    pts = [{"a": i, "b": 1} for i in range(n)]
    results = [p.result() for p in ex.as_completed(ex.submit(pts))]
    assert all(r.value == value_of(r.point) for r in results)
    return n


def test_speculation_duplicate_wins_loser_discarded(tmp_path):
    slow_obj, fast_obj, s_slow, s_fast, ex, corpus = \
        _speculation_fleet(tmp_path)
    pool = ex.remote_pool
    n_warm = _warmup(ex)
    hold = slow_obj.gate(9, 9)  # stalls ONLY on the slow worker
    # both workers idle -> the dispatcher picks the first (slow) one
    (pend,) = ex.submit([{"a": 9, "b": 9}])
    wait_until(lambda: (9, 9) in slow_obj.calls, msg="dispatch to straggler")
    # the monitor notices the straggler and duplicates it onto the fast
    # worker, which resolves it: the driver is unblocked by speculation
    done = ex.next_completed([pend])
    assert done.result().value == value_of({"a": 9, "b": 9})
    assert (9, 9) in fast_obj.calls
    assert pool.speculations == 1 and pool.speculation_wins == 1
    # now the straggler finishes: its result is a loser, discarded
    hold.set()
    wait_until(lambda: pool.losers_discarded == 1, msg="loser discard")
    ex.close()
    # exactly-once everywhere: one memo entry, one corpus record, and
    # the history-facing future resolved a single time (pend.done())
    recs = TuningCorpus(tmp_path / "corpus.json", job_id="x").records()
    keyed = [tuple(sorted(r["point"].items())) for r in recs]
    assert len(keyed) == len(set(keyed)) == n_warm + 1
    s_slow.stop()
    s_fast.stop()


def test_speculation_original_wins_duplicate_discarded(tmp_path):
    slow_obj, fast_obj, s_slow, s_fast, ex, corpus = \
        _speculation_fleet(tmp_path)
    pool = ex.remote_pool
    n_warm = _warmup(ex)
    hold_orig = slow_obj.gate(8, 8)
    hold_dup = fast_obj.gate(8, 8)  # the duplicate stalls too
    (pend,) = ex.submit([{"a": 8, "b": 8}])
    wait_until(lambda: (8, 8) in slow_obj.calls, msg="dispatch to straggler")
    wait_until(lambda: pool.speculations == 1, msg="duplicate dispatch")
    assert pool.speculating == 1  # both copies live right now
    hold_orig.set()  # the ORIGINAL finishes first this time
    done = ex.next_completed([pend])
    assert done.result().value == value_of({"a": 8, "b": 8})
    assert pool.speculation_wins == 0  # the straggler finished after all
    hold_dup.set()
    wait_until(lambda: pool.losers_discarded == 1, msg="duplicate discard")
    assert pool.speculating == 0
    ex.close()
    recs = TuningCorpus(tmp_path / "corpus.json", job_id="x").records()
    keyed = [tuple(sorted(r["point"].items())) for r in recs]
    assert len(keyed) == len(set(keyed)) == n_warm + 1
    s_slow.stop()
    s_fast.stop()


def test_killing_the_speculating_worker_loses_nothing(tmp_path):
    """SIGKILL-shaped death of the worker holding the duplicate: the
    original copy still resolves the task; 0 lost, 0 double-recorded."""
    slow_obj, fast_obj, s_slow, s_fast, ex, corpus = \
        _speculation_fleet(tmp_path)
    pool = ex.remote_pool
    n_warm = _warmup(ex)
    hold_orig = slow_obj.gate(7, 7)
    fast_obj.gate(7, 7)  # duplicate blocks forever (its host dies)
    (pend,) = ex.submit([{"a": 7, "b": 7}])
    wait_until(lambda: (7, 7) in slow_obj.calls, msg="dispatch to straggler")
    wait_until(lambda: pool.speculations == 1, msg="duplicate dispatch")
    s_fast.stop()  # hard death of the speculating worker
    wait_until(lambda: pool.alive_workers() == 1, msg="death detection")
    hold_orig.set()
    done = ex.next_completed([pend])
    assert done.result().value == value_of({"a": 7, "b": 7})
    ex.close()
    recs = TuningCorpus(tmp_path / "corpus.json", job_id="x").records()
    keyed = [tuple(sorted(r["point"].items())) for r in recs]
    assert len(keyed) == len(set(keyed)) == n_warm + 1
    s_slow.stop()


def test_history_identical_with_and_without_speculation(tmp_path):
    """Speculation must be invisible in the recorded trace: the same
    deterministic objective tuned with speculation on (and firing) vs
    off yields identical (point, value, cost, fidelity) observations."""

    def run(spec: bool, straggle: bool):
        obj_a, obj_b = GatedObjective(), GatedObjective()
        hold = obj_a.gate(2, 1) if straggle else None
        if straggle:  # release the straggler once its duplicate won
            threading.Timer(2.0, hold.set).start()
        sa = WorkerServer(obj_a, slots=1, heartbeat_s=0.1).start()
        sb = WorkerServer(obj_b, slots=1, heartbeat_s=0.1).start()
        tc = TunerConfig(algorithm="random", budget=8, seed=7,
                         executor=ExecutorConfig(
                             workers=[sa.address, sb.address]))
        tc.executor.speculation = spec
        tc.executor.speculation_factor = 2.0
        tc.executor.speculation_min_observations = 3
        tuner = Tuner(obj_a, small_space(), tc)
        hist = tuner.run()
        tuner.close()
        if hold is not None:
            hold.set()
        sa.stop()
        sb.stop()
        return sorted((tuple(sorted(e.point.items())), e.value,
                       e.cost_seconds, e.fidelity) for e in hist.evals)

    baseline = run(spec=False, straggle=False)
    with_spec = run(spec=True, straggle=True)
    assert with_spec == baseline


# ---------------------------------------------------------------------------
# hardware-aware scheduling: strict pinning vs normalize calibration
# ---------------------------------------------------------------------------

def test_strict_homogeneity_refuses_mixed_static_fleet():
    obj = GatedObjective()
    s1 = WorkerServer(obj, slots=1, heartbeat_s=0.1,
                      fingerprint={"kind": "A"}).start()
    s2 = WorkerServer(obj, slots=1, heartbeat_s=0.1,
                      fingerprint={"kind": "B"}).start()
    with pytest.raises(ConnectionError, match="strict homogeneity"):
        RemoteWorkerPool([s1.address, s2.address])
    s1.stop()
    s2.stop()


def test_strict_homogeneity_rejects_mismatched_joiner():
    obj = GatedObjective()
    s1 = WorkerServer(obj, slots=1, heartbeat_s=0.1,
                      fingerprint={"kind": "A"}).start()
    pool = RemoteWorkerPool([s1.address])
    alien = WorkerServer(obj, slots=1, heartbeat_s=0.1,
                         fingerprint={"kind": "B"})
    alien.start_join(local(pool))
    wait_until(lambda: pool.rejected_joins == 1, msg="join rejection")
    assert pool.parallelism == 1  # the run continues on its partition
    twin = WorkerServer(obj, slots=1, heartbeat_s=0.1,
                        fingerprint={"kind": "A"})
    twin.start_join(local(pool))
    wait_until(lambda: pool.parallelism == 2, msg="matching join")
    pool.shutdown()
    s1.stop()
    alien.stop()
    twin.stop()


def test_unknown_fingerprint_admissible_under_strict():
    """A v1 / pre-elastic daemon reports no fingerprint; 'did not
    report' must not be treated as different hardware."""
    obj = GatedObjective()
    lsock = socket.create_server(("127.0.0.1", 0))
    port = lsock.getsockname()[1]

    def v1_worker():
        conn, _ = lsock.accept()
        recv_msg(conn)  # hello
        send_msg(conn, {"type": "register", "protocol": 1, "slots": 1,
                        "heartbeat_s": 0.1})
        while True:  # beat until the pool says bye / closes
            try:
                send_msg(conn, {"type": "heartbeat"})
            except OSError:
                return
            time.sleep(0.05)

    threading.Thread(target=v1_worker, daemon=True).start()
    s1 = WorkerServer(obj, slots=1, heartbeat_s=0.1,
                      fingerprint={"kind": "A"}).start()
    pool = RemoteWorkerPool([f"127.0.0.1:{port}", s1.address])
    assert pool.parallelism == 2
    assert pool.fleet_stats()["partition"] == fingerprint_id({"kind": "A"})
    health = {w["address"]: w for w in pool.fleet_health()}
    assert health[f"127.0.0.1:{port}"]["partition"] == UNKNOWN_PARTITION
    pool.shutdown()
    s1.stop()
    lsock.close()


def test_normalize_admits_mixed_fleet_and_calibrates_cost(tmp_path):
    obj_a, obj_b = GatedObjective(), GatedObjective()
    sa = WorkerServer(obj_a, slots=1, heartbeat_s=0.1,
                      fingerprint={"kind": "A"}).start()
    sb = WorkerServer(obj_b, slots=1, heartbeat_s=0.1,
                      fingerprint={"kind": "B"}).start()
    ex = EvaluationExecutor(
        obj_a, small_space(), workers=[sa.address, sb.address],
        fleet=FleetOptions(speculation=False, homogeneity="normalize"))
    pool = ex.remote_pool
    fp_a, fp_b = fingerprint_id({"kind": "A"}), fingerprint_id({"kind": "B"})
    assert pool.parallelism == 2  # both admitted
    # one duplicate pair: partition B measured the same task 2x slower
    pool._calibration.observe(fp_a, 1.0, fp_b, 2.0)
    assert pool._calibration.factor(fp_b) == pytest.approx(0.5)
    (snap,) = pool.fleet_stats()["calibration"]
    assert snap == {"partition": fp_b, "reference": fp_a,
                    "ratio": 0.5, "n_pairs": 1}
    # a result measured on B is rescaled into reference seconds and
    # stamped with the factor; GatedObjective declares cost 0.01
    hold = obj_a.gate(9, 9)  # pin worker A so (5, 5) lands on B
    ex.submit([{"a": 9, "b": 9}])
    wait_until(lambda: (9, 9) in obj_a.calls, msg="A busy")
    (pend,) = ex.submit([{"a": 5, "b": 5}])
    done = ex.next_completed([pend])
    assert (5, 5) in obj_b.calls
    assert done.result().meta["cost_calibration"] == pytest.approx(0.5)
    assert done.result().cost_seconds == pytest.approx(0.005)
    hold.set()
    ex.close()
    sa.stop()
    sb.stop()


def test_calibration_ignores_pairs_off_reference():
    from repro.tuning.remote import _FleetCalibration

    cal = _FleetCalibration(reference="ref0")
    cal.observe("p1", 1.0, "p2", 2.0)   # no reference side: ignored
    cal.observe("ref0", 1.0, "ref0", 2.0)  # same partition: ignored
    cal.observe("ref0", 0.0, "p1", 2.0)    # non-positive: ignored
    assert cal.factor("p1") == 1.0 and cal.snapshot() == []
    cal.observe("ref0", 1.0, "p1", 4.0)
    cal.observe("p1", 1.0, "ref0", 1.0)  # order-insensitive
    assert cal.factor("p1") == pytest.approx((0.25 * 1.0) ** 0.5)


# ---------------------------------------------------------------------------
# per-worker heartbeat stall windows
# ---------------------------------------------------------------------------

def test_stall_window_derives_from_registered_heartbeat():
    obj = GatedObjective()
    s1 = WorkerServer(obj, slots=1, heartbeat_s=0.5).start()
    pool = RemoteWorkerPool([s1.address])
    assert pool._workers[0].heartbeat_timeout == pytest.approx(1.5)
    pool.shutdown()
    s1.stop()


def test_fleet_heartbeat_fallback_for_undeclared_workers():
    lsock = socket.create_server(("127.0.0.1", 0))
    port = lsock.getsockname()[1]

    def mute_worker():
        conn, _ = lsock.accept()
        recv_msg(conn)
        send_msg(conn, {"type": "register", "protocol": 1, "slots": 1})
        time.sleep(5.0)

    threading.Thread(target=mute_worker, daemon=True).start()
    pool = RemoteWorkerPool([f"127.0.0.1:{port}"],
                            fleet=FleetOptions(heartbeat_s=0.6))
    assert pool._workers[0].heartbeat_timeout == pytest.approx(1.8)
    pool.shutdown()
    lsock.close()


# ---------------------------------------------------------------------------
# stale-capacity regression: the MF drain survives a mid-drain kill
# ---------------------------------------------------------------------------

class FidelityObjective(Evaluator):
    """Fidelity-aware, deterministic, slow enough to be killed mid-run."""

    supports_fidelity = True

    def __init__(self, seconds=0.1):
        self.seconds = seconds

    def __call__(self, p, fidelity=None):
        time.sleep(self.seconds)
        return value_of(p), {"src": "worker", "fidelity": fidelity}


def test_multi_fidelity_drain_survives_worker_kill():
    obj1, obj2 = FidelityObjective(), FidelityObjective()
    s1 = WorkerServer(obj1, slots=2, heartbeat_s=0.1).start()
    s2 = WorkerServer(obj2, slots=2, heartbeat_s=0.1).start()
    tc = TunerConfig(algorithm="random", budget=6, seed=3,
                     multi_fidelity=True,
                     executor=ExecutorConfig(
                         workers=[s1.address, s2.address]))
    tc.executor.speculation = False
    tuner = Tuner(obj1, small_space(), tc)
    # a host dies while rungs are filling/draining: capacity must be
    # re-read live (the dead slots vanish) and its tasks reinjected —
    # the drain completes instead of deadlocking on phantom slots
    threading.Timer(0.25, s2.stop).start()
    hist = tuner.run()
    assert len(hist) > 0
    assert all(e.value == value_of(e.point) for e in hist.evals)
    wait_until(lambda: tuner.executor.parallelism == 2,
               msg="dead slots leaving the live capacity")
    tuner.close()
    s1.stop()


def test_slot_cap_governor_tracks_live_capacity():
    """The fair-share cap composes with live fleet capacity: capacity
    shrinking below the cap must shrink advertised parallelism too."""
    obj = GatedObjective()
    s1 = WorkerServer(obj, slots=2, heartbeat_s=0.1).start()
    s2 = WorkerServer(obj, slots=2, heartbeat_s=0.1).start()
    ex = EvaluationExecutor(obj, small_space(),
                            workers=[s1.address, s2.address],
                            fleet=FleetOptions(speculation=False))
    ex.slot_cap = 3
    assert ex.parallelism == 3  # min(cap, live 4)
    s2.stop()
    wait_until(lambda: ex.parallelism == 2, msg="cap re-reads live fleet")
    ex.close()
    s1.stop()
