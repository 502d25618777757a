"""Remote measurement workers: wire protocol framing, the
RemoteWorkerPool executor backend (submit/next_completed/preempt over
TCP), worker-death reinjection with exactly-once recording, per-eval
timeouts across the wire, heartbeat stall detection, and end-to-end
Tuner runs (async loop and multi-fidelity) against an in-process worker
fleet."""
import json
import math
import socket
import struct
import threading
import time

import pytest

from repro.core import ExecutorConfig, IntDim, SearchSpace, Tuner, TunerConfig
from repro.launch.worker import resolve_objective
from repro.tuning.cache import JsonCacheStore
from repro.tuning.executor import EvaluationExecutor
from repro.tuning.objective import CountingEvaluator, Evaluator
from repro.tuning.remote import (
    PROTOCOL_VERSION,
    RemoteWorkerPool,
    WorkerServer,
    parse_address,
    recv_msg,
    send_msg,
)


def small_space() -> SearchSpace:
    return SearchSpace([IntDim("a", 0, 20), IntDim("b", 0, 9)])


def value_of(p) -> float:
    return float(p["a"] * 10 + p["b"])


class SleepyObjective(Evaluator):
    """Deterministic value, configurable sleep, thread-safe call log."""

    def __init__(self, seconds=0.0):
        self.seconds = seconds
        self.calls = []
        self._lock = threading.Lock()

    def __call__(self, p, fidelity=None):
        time.sleep(self.seconds)
        with self._lock:
            self.calls.append((p["a"], p["b"]))
        return value_of(p), {"src": "worker"}


# ---------------------------------------------------------------------------
# framing + address/objective resolution
# ---------------------------------------------------------------------------

def test_framing_roundtrip_including_nonfinite():
    a, b = socket.socketpair()
    try:
        msgs = [
            {"type": "task", "id": 1, "point": {"a": 3}, "fidelity": None},
            {"type": "result", "id": 1, "value": -math.inf,
             "seconds": 0.25, "meta": {"error": "OOM", "nan": math.nan}},
        ]
        for m in msgs:
            send_msg(a, m)
        got1 = recv_msg(b)
        got2 = recv_msg(b)
        assert got1 == msgs[0]
        assert got2["value"] == -math.inf  # failed-config score survives
        assert math.isnan(got2["meta"]["nan"])
    finally:
        a.close()
        b.close()


def test_framing_peer_close_raises_connection_error():
    a, b = socket.socketpair()
    a.sendall(struct.pack(">I", 100) + b"short")  # truncated frame
    a.close()
    with pytest.raises(ConnectionError):
        recv_msg(b)
    b.close()


def test_framing_rejects_oversized_and_non_object_frames():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">I", 1 << 30))
        with pytest.raises(ValueError):
            recv_msg(b)
    finally:
        a.close()
        b.close()
    a, b = socket.socketpair()
    try:
        payload = json.dumps([1, 2, 3]).encode()
        a.sendall(struct.pack(">I", len(payload)) + payload)
        with pytest.raises(ValueError):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_parse_address():
    assert parse_address("localhost:9123") == ("localhost", 9123)
    assert parse_address("::1:9123") == ("::1", 9123)  # v6: last colon splits
    with pytest.raises(ValueError):
        parse_address("no-port")
    with pytest.raises(ValueError):
        parse_address(":9123")


def _plain_objective(p):
    return float(p["a"])


def _factory():
    return SleepyObjective()


def test_resolve_objective_specs():
    fn = resolve_objective("tests.test_remote:_plain_objective")
    # identity can differ (the test module imports under two names), but
    # it must be the same function object semantically
    assert fn.__name__ == "_plain_objective" and fn({"a": 4}) == 4.0
    made = resolve_objective("tests.test_remote:_factory()")
    assert type(made).__name__ == "SleepyObjective"  # factory was called
    with pytest.raises(ValueError):
        resolve_objective("justamodule")


# ---------------------------------------------------------------------------
# pool + executor over a live in-process fleet
# ---------------------------------------------------------------------------

@pytest.fixture
def fleet():
    """Two workers (slots 1 + 2) serving SleepyObjective; yields
    (objective, [servers]); servers are torn down afterwards."""
    obj = SleepyObjective(seconds=0.01)
    servers = [WorkerServer(obj, slots=1, heartbeat_s=0.2).start(),
               WorkerServer(obj, slots=2, heartbeat_s=0.2).start()]
    yield obj, servers
    for s in servers:
        s.stop()


def test_remote_executor_roundtrip_and_memo(fleet):
    obj, servers = fleet
    space = small_space()
    ex = EvaluationExecutor(obj, space,
                            workers=[s.address for s in servers])
    assert ex.backend == "remote"
    assert ex.parallelism == 3  # fleet slot total: 1 + 2
    pts = [{"a": i, "b": i % 3} for i in range(6)]
    got = {tuple(sorted(p.point.items())): p.result()
           for p in ex.as_completed(ex.submit(pts))}
    assert len(got) == 6
    for r in got.values():
        assert r.value == value_of(r.point)
        assert r.meta["src"] == "worker"  # worker meta crossed the wire
    assert len(obj.calls) == 6
    # memo: a repeat submit resolves instantly, nothing re-measured
    again = ex.submit(pts)
    assert all(p.done() and p.result().meta.get("memoized") for p in again)
    assert len(obj.calls) == 6
    ex.close()


def test_remote_inflight_aliasing_shares_measurement():
    obj = SleepyObjective(seconds=0.15)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address])
    p = {"a": 5, "b": 1}
    first = ex.submit([p])
    second = ex.submit([p])  # same key while in flight: shares the future
    assert second[0].future is first[0].future
    done = list(ex.as_completed(first + second))
    assert len(done) == 2
    assert len(obj.calls) == 1  # one real measurement
    assert {d.result().value for d in done} == {value_of(p)}
    ex.close()
    server.stop()


def test_remote_preempt_queued_is_cancelled_and_unrecorded(tmp_path):
    obj = SleepyObjective(seconds=0.2)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    path = str(tmp_path / "memo.json")
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address],
                            cache_path=path)
    running, queued = ex.submit([{"a": 1, "b": 0}, {"a": 2, "b": 0}])
    time.sleep(0.05)  # let the dispatcher hand task 1 to the only slot
    verdict = ex.preempt(queued)
    assert verdict == "cancelled"
    assert queued.result().meta == {"preempted": True}
    done = ex.next_completed([running])
    assert done.result().value == value_of(running.point)
    ex.close()
    # the preempted point was never measured, never cached, not persisted
    assert (2, 0) not in obj.calls
    stored = JsonCacheStore(path).load()
    assert all(json.loads(k)[0] != 2 for k in stored)
    server.stop()


def test_remote_preempt_running_lets_it_finish():
    obj = SleepyObjective(seconds=0.15)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address])
    (pend,) = ex.submit([{"a": 7, "b": 2}])
    time.sleep(0.05)  # dispatched: the worker already started measuring
    assert ex.preempt(pend) == "running"
    done = ex.next_completed([pend])
    assert done is pend and done.result().value == value_of(pend.point)
    assert len(obj.calls) == 1  # paid-for measurement recorded exactly once
    ex.close()
    server.stop()


def test_remote_timeout_holds_across_the_wire(tmp_path):
    obj = SleepyObjective(seconds=0.6)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    path = str(tmp_path / "memo.json")
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address],
                            timeout=0.15, cache_path=path)
    (pend,) = ex.submit([{"a": 3, "b": 3}])
    time.sleep(0.05)  # ensure it was dispatched (not resolved inline)
    t0 = time.perf_counter()
    done = ex.next_completed([pend])
    waited = time.perf_counter() - t0
    assert done.result().value == -math.inf
    assert done.result().meta.get("timeout")
    assert waited < 0.5  # resolved at the deadline, not at worker pace
    ex.close()
    # a timeout verdict reflects this run's setting: never persisted
    assert JsonCacheStore(path).load() == {}
    server.stop()


def test_remote_worker_death_reinjects_not_fails():
    obj = SleepyObjective(seconds=0.08)
    s1 = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    s2 = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    ex = EvaluationExecutor(obj, small_space(),
                            workers=[s1.address, s2.address])
    pend = ex.submit([{"a": i, "b": 0} for i in range(8)])
    threading.Timer(0.1, s2.stop).start()  # a host dies mid-run
    results = [p.result() for p in ex.as_completed(pend)]
    assert len(results) == 8
    # every point got a real measurement — a disconnect is a fleet
    # property, never recorded as a configuration failure
    for r in results:
        assert r.value == value_of(r.point), r.point
    # exactly-once: no point was recorded twice even though reinjection
    # may re-measure one the dead worker had started
    keys = [tuple(sorted(r.point.items())) for r in results]
    assert len(keys) == len(set(keys))
    assert ex._pool.alive_workers() == 1
    ex.close()
    s1.stop()


def test_remote_whole_fleet_down_fails_loudly():
    obj = SleepyObjective(seconds=0.3)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.2).start()
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address])
    pend = ex.submit([{"a": 1, "b": 1}, {"a": 2, "b": 2}])
    time.sleep(0.05)
    server.stop()  # no survivors: the run cannot proceed
    with pytest.raises(ConnectionError):
        for _ in ex.as_completed(pend):
            pass
    ex.close()


def test_remote_objective_exception_scores_minus_inf():
    def boom(p):
        raise RuntimeError("OOM")

    server = WorkerServer(boom, slots=1, heartbeat_s=0.2).start()
    ex = EvaluationExecutor(boom, small_space(), workers=[server.address])
    (pend,) = ex.submit([{"a": 1, "b": 0}])
    r = ex.next_completed([pend]).result()
    assert r.value == -math.inf
    assert "OOM" in r.meta["error"]  # failure crossed as a result,
    ex.close()                       # not as a protocol error
    server.stop()


def test_remote_unreachable_worker_fails_fast():
    with pytest.raises(ConnectionError):
        RemoteWorkerPool(["127.0.0.1:1"], connect_timeout=0.3)


def test_remote_submit_after_fleet_death_raises_not_hangs():
    """A task enqueued with no live worker would never resolve; submit
    must refuse loudly instead of letting the driver wait forever."""
    obj = SleepyObjective(seconds=0.01)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.1).start()
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address])
    server.stop()
    deadline = time.time() + 5
    while ex._pool.alive_workers() and time.time() < deadline:
        time.sleep(0.01)  # wait for the pool to notice the EOF
    with pytest.raises(ConnectionError):
        ex.submit([{"a": 1, "b": 1}])
    ex.close()


def test_remote_capacity_shrinks_when_a_worker_dies():
    obj = SleepyObjective(seconds=0.01)
    s1 = WorkerServer(obj, slots=2, heartbeat_s=0.1).start()
    s2 = WorkerServer(obj, slots=2, heartbeat_s=0.1).start()
    ex = EvaluationExecutor(obj, small_space(),
                            workers=[s1.address, s2.address])
    assert ex.parallelism == 4
    s2.stop()
    deadline = time.time() + 5
    while ex.parallelism != 2 and time.time() < deadline:
        time.sleep(0.01)
    # the driver's in-flight window follows the live fleet, so dead
    # slots are not advertised and tasks don't starve in the queue
    assert ex.parallelism == 2
    ex.close()
    s1.stop()


def test_stray_connection_does_not_wedge_worker():
    """Sessions are serial, so a connection that never says hello (port
    scan, health probe) must be dropped by the handshake timeout and the
    real tuner served afterwards."""
    obj = SleepyObjective(seconds=0.01)
    server = WorkerServer(obj, slots=1, heartbeat_s=0.2)
    server.handshake_timeout_s = 0.3  # fast test; default is 10s
    server.start()
    stray = socket.create_connection((server.host, server.port))
    time.sleep(0.05)  # the worker is now blocked reading stray's hello
    ex = EvaluationExecutor(obj, small_space(), workers=[server.address])
    (pend,) = ex.submit([{"a": 4, "b": 4}])
    assert ex.next_completed([pend]).result().value == value_of(pend.point)
    ex.close()
    stray.close()
    server.stop()


def test_worker_survives_tuner_restart(fleet):
    obj, servers = fleet
    space = small_space()
    for round_ in range(2):
        ex = EvaluationExecutor(obj, space, workers=[servers[0].address])
        (pend,) = ex.submit([{"a": round_, "b": round_}])
        assert ex.next_completed([pend]).result().value == value_of(
            pend.point)
        ex.close()
    assert servers[0].sessions_served == 2


def test_heartbeat_stall_marks_worker_dead():
    """A worker that registers then goes silent (hung host, not a closed
    socket) is detected via missed heartbeats and its task reinjected."""
    lsock = socket.create_server(("127.0.0.1", 0))
    frozen_port = lsock.getsockname()[1]

    def frozen_worker():
        conn, _ = lsock.accept()
        recv_msg(conn)  # hello
        send_msg(conn, {"type": "register", "protocol": PROTOCOL_VERSION,
                        "slots": 1, "heartbeat_s": 0.05})
        recv_msg(conn)  # accept one task, then never respond, never beat
        time.sleep(5.0)

    threading.Thread(target=frozen_worker, daemon=True).start()
    obj = SleepyObjective(seconds=0.02)
    healthy = WorkerServer(obj, slots=1, heartbeat_s=0.05).start()
    ex = EvaluationExecutor(
        obj, small_space(),
        workers=[f"127.0.0.1:{frozen_port}", healthy.address])
    # 2 tasks: one lands on the frozen worker, one on the healthy one
    pend = ex.submit([{"a": 1, "b": 1}, {"a": 2, "b": 2}])
    results = [p.result() for p in ex.as_completed(pend)]
    assert sorted(r.value for r in results) == sorted(
        value_of(p.point) for p in pend)
    assert ex._pool.alive_workers() == 1
    ex.close()
    healthy.stop()
    lsock.close()


# ---------------------------------------------------------------------------
# end to end through the Tuner
# ---------------------------------------------------------------------------

def test_tuner_remote_backend_end_to_end(tmp_path):
    obj = SleepyObjective(seconds=0.005)
    servers = [WorkerServer(obj, slots=2, heartbeat_s=0.2).start()
               for _ in range(2)]
    path = str(tmp_path / "memo.json")
    t = Tuner(obj, small_space(),
              TunerConfig(algorithm="random", budget=12, seed=0,
                          verbose=False,
                          executor=ExecutorConfig(
                              memo_cache_path=path,
                              workers=[s.address for s in servers])))
    assert t.executor.backend == "remote"
    assert t.executor.parallelism == 4
    h = t.run()
    t.close()
    assert len(h) == 12
    assert all(e.value == value_of(e.point) for e in h.evals)

    # the memo was written BY THE TUNER HOST (workers share no
    # filesystem with the store) and is honored across backends: a
    # second run on the local thread backend re-evaluates nothing
    counting = CountingEvaluator(lambda p: value_of(p))
    t2 = Tuner(counting, small_space(),
               TunerConfig(algorithm="random", budget=12, seed=0,
                           verbose=False,
                           executor=ExecutorConfig(parallelism=2,
                                                   memo_cache_path=path)))
    h2 = t2.run()
    t2.close()
    assert counting.calls == 0
    assert sorted(e.value for e in h2.evals) == sorted(
        e.value for e in h.evals)
    for s in servers:
        s.stop()


def test_tuner_remote_multi_fidelity_composes():
    class FidObjective(Evaluator):
        supports_fidelity = True

        def __init__(self):
            self.log = []
            self._lock = threading.Lock()

        def __call__(self, p, fidelity=None):
            f = 1.0 if fidelity is None else float(fidelity)
            time.sleep(0.01 * f)
            v = value_of(p) + (1.0 - f) * ((p["a"] * 7) % 5 - 2)
            with self._lock:
                self.log.append((p["a"], p["b"], round(f, 9)))
            return v, {"cost_seconds": 0.01 * f}

    obj = FidObjective()
    servers = [WorkerServer(obj, slots=2, heartbeat_s=0.2).start()
               for _ in range(2)]
    t = Tuner(obj, small_space(),
              TunerConfig(algorithm="random", budget=6, seed=0,
                          verbose=False, multi_fidelity=True,
                          executor=ExecutorConfig(
                              workers=[s.address for s in servers])))
    h = t.run()
    stats = t.rung_scheduler.stats()
    t.close()
    # rungs actually ran at partial fidelity over the wire
    assert any(e.fidelity < 1.0 for e in h.evals)
    assert stats[0]["completed"] > 0
    # exactly-once: every real worker-side measurement is recorded once
    measured = [e for e in h.evals if not e.meta.get("memoized")]
    assert len(measured) == len(obj.log)
    keys = [(e.point["a"], e.point["b"], round(e.fidelity, 9))
            for e in measured]
    assert len(keys) == len(set(keys))
    for s in servers:
        s.stop()
