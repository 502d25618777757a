"""Disk-backed memo persistence: JsonCacheStore atomicity + locking,
MemoCache round-trips across executor instances, concurrent writers
merging instead of clobbering, the 0-re-evaluation guarantee for a
repeated tuning run — plus the hardening contracts: corrupt-file
quarantine, loud serialization failure at put time (no default=str
corruption), batched flushes (one store write per completion drain),
cross-process contention, and the guarantee that timeout/preempt
placeholders never reach the disk store."""
import json
import math
import multiprocessing
import time
from concurrent.futures import ThreadPoolExecutor, wait

import pytest

from repro.core import ExecutorConfig, IntDim, SearchSpace, Tuner, TunerConfig
from repro.tuning.cache import JsonCacheStore, NullCacheStore, open_store
from repro.tuning.executor import EvalResult, EvaluationExecutor, MemoCache
from repro.tuning.objective import CountingEvaluator


def small_space() -> SearchSpace:
    return SearchSpace([IntDim("a", 0, 9), IntDim("b", 0, 9)])


def evaluate_all(ex, points):
    """Submit ``points``, let every dispatched measurement finish, then
    drain them all; results in submission order."""
    pendings = ex.submit(points)
    wait([p.future for p in pendings if p.future is not None])
    for _ in ex.as_completed(pendings):
        pass
    return [p.result() for p in pendings]


# ---------------------------------------------------------------------------
# store layer
# ---------------------------------------------------------------------------

def test_json_store_roundtrip_and_merge(tmp_path):
    store = JsonCacheStore(tmp_path / "c.json")
    assert store.load() == {}
    store.put("k1", {"v": 1})
    store.put("k2", {"v": 2})
    assert store.load() == {"k1": {"v": 1}, "k2": {"v": 2}}
    # a second store instance on the same path merges, not clobbers
    other = JsonCacheStore(tmp_path / "c.json")
    other.put("k3", {"v": 3})
    assert set(store.load()) == {"k1", "k2", "k3"}
    # no torn temp files left behind
    assert not (tmp_path / "c.json.tmp").exists()


def test_json_store_neg_inf_value_roundtrip(tmp_path):
    """Failed configurations (-inf) must survive the JSON round trip."""
    store = JsonCacheStore(tmp_path / "c.json")
    store.put("oom", {"value": -math.inf, "point": {"a": 1}})
    assert store.load()["oom"]["value"] == -math.inf


def test_json_store_concurrent_writers_union(tmp_path):
    """N writers, each with its own store instance, racing read-merge-write
    on one file: the flock serializes them and every key survives."""
    path = tmp_path / "c.json"

    def writer(wid):
        store = JsonCacheStore(path)  # own fd, contends on the lock file
        for i in range(5):
            store.put(f"w{wid}-{i}", {"wid": wid, "i": i})

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(writer, range(8)))
    data = JsonCacheStore(path).load()
    assert len(data) == 40
    assert json.loads(path.read_text()) == data  # file itself is coherent


def test_corrupt_cache_file_is_quarantined_not_fatal(tmp_path):
    """A torn/corrupt cache file (host died mid-write) must not kill the
    run: it is renamed to .corrupt, a warning fires, and the store
    continues empty."""
    path = tmp_path / "c.json"
    path.write_text('{"k1": {"v": 1}, "k2": TORN')  # mid-write death
    store = JsonCacheStore(path)
    with pytest.warns(RuntimeWarning, match="quarantined"):
        assert store.load() == {}
    quarantined = tmp_path / "c.json.corrupt"
    assert quarantined.exists()  # kept for post-mortem, byte-identical
    assert quarantined.read_text() == '{"k1": {"v": 1}, "k2": TORN'
    assert not path.exists()
    # and the store is fully usable afterwards
    store.put("k3", {"v": 3})
    assert store.load() == {"k3": {"v": 3}}


def test_corrupt_cache_file_during_put_recovers(tmp_path):
    """put() read-merges under the lock; a corrupt file there is
    quarantined too and the put still lands."""
    path = tmp_path / "c.json"
    path.write_text("not json at all")
    store = JsonCacheStore(path)
    with pytest.warns(RuntimeWarning):
        store.put("k", {"v": 1})
    assert json.loads(path.read_text()) == {"k": {"v": 1}}


def test_non_serializable_record_fails_loudly_at_put(tmp_path):
    """default=str used to silently stringify non-JSON fields — the
    record *looked* cached but reloaded corrupted.  Now it's a TypeError
    naming the key, and the store file is untouched."""
    store = JsonCacheStore(tmp_path / "c.json")
    store.put("good", {"v": 1})
    with pytest.raises(TypeError, match="badkey"):
        store.put("badkey", {"v": object()})
    with pytest.raises(TypeError, match="round trip"):
        store.put_many({"k1": {"v": 2}, "k2": {"v": {1, 2}}})
    # json.dumps would SUCCEED on these — and corrupt them on reload
    # (tuple -> list, int key -> str key); they must be rejected too
    with pytest.raises(TypeError, match="tuple"):
        store.put("tup", {"tile": (512, 128)})
    with pytest.raises(TypeError, match="non-string key"):
        store.put("intkey", {"meta": {1: "x"}})
    assert store.load() == {"good": {"v": 1}}  # nothing half-written


def test_memo_cache_rejects_non_serializable_meta_at_put_time(tmp_path):
    """Buffered mode must surface the error at put() — pointing at the
    evaluation that produced the bad record — not at some later flush."""
    space = small_space()
    cache = MemoCache(store=JsonCacheStore(tmp_path / "m.json"),
                      autoflush=False)
    with pytest.raises(TypeError, match="round trip"):
        cache.put(space.key({"a": 1, "b": 1}),
                  EvalResult({"a": 1, "b": 1}, 1.0, 0.1,
                             {"handle": object()}))
    cache.flush()
    assert JsonCacheStore(tmp_path / "m.json").load() == {}


def test_cached_record_reloads_equal_to_what_was_stored(tmp_path):
    """Regression for the default=str corruption: a record must
    round-trip *equal*, including non-finite floats and nesting."""
    space = small_space()
    meta = {"roofline": {"compute_s": 0.125, "fits": True},
            "notes": ["a", 1, 2.5, None], "err": -math.inf}
    rec = EvalResult({"a": 3, "b": 4}, -math.inf, 1.5, meta)
    cache = MemoCache(store=JsonCacheStore(tmp_path / "m.json"))
    cache.put(space.key(rec.point), rec)
    fresh = MemoCache(store=JsonCacheStore(tmp_path / "m.json"))
    fresh.load_store(space)
    hit = fresh.get(space.key(rec.point))
    assert hit.point == rec.point
    assert hit.value == rec.value
    assert hit.cost_seconds == rec.cost_seconds
    assert hit.meta == rec.meta  # exact, not stringified


def test_open_store_dispatch(tmp_path):
    assert isinstance(open_store(None), NullCacheStore)
    assert isinstance(open_store(tmp_path / "x.json"), JsonCacheStore)
    null = open_store(None)
    null.put("k", {})
    assert null.load() == {}


# ---------------------------------------------------------------------------
# MemoCache on top of the store
# ---------------------------------------------------------------------------

def test_memo_cache_disk_roundtrip(tmp_path):
    space = small_space()
    store = JsonCacheStore(tmp_path / "memo.json")
    cache = MemoCache(store=store)
    cache.put(space.key({"a": 1, "b": 2}),
              EvalResult({"a": 1, "b": 2}, 5.0, 0.25, {"m": 1}))
    # a fresh cache (new process, conceptually) seeds itself from disk
    fresh = MemoCache(store=JsonCacheStore(tmp_path / "memo.json"))
    assert fresh.load_store(space) == 1
    hit = fresh.get(space.key({"a": 1, "b": 2}))
    assert hit.value == 5.0 and hit.cost_seconds == 0.25 and hit.meta == {"m": 1}


def test_executor_memo_survives_restart(tmp_path):
    """A new executor pointed at the same cache file re-evaluates nothing."""
    space = small_space()
    path = str(tmp_path / "memo.json")
    counting = CountingEvaluator(lambda p: float(p["a"] * 10 + p["b"]))
    pts = [{"a": i, "b": i} for i in range(4)]

    ex1 = EvaluationExecutor(counting, space, parallelism=2, cache_path=path)
    out1 = evaluate_all(ex1, pts)
    ex1.close()
    assert counting.calls == 4

    ex2 = EvaluationExecutor(counting, space, parallelism=2, cache_path=path)
    out2 = evaluate_all(ex2, pts)
    ex2.close()
    assert counting.calls == 4  # zero re-evaluations
    assert [r.value for r in out2] == [r.value for r in out1]
    assert all(r.meta.get("memoized") for r in out2)


def test_executor_submit_next_completed_with_disk_cache(tmp_path):
    """The completion-driven protocol hits the disk cache too: cached
    submissions come back already done."""
    space = small_space()
    path = str(tmp_path / "memo.json")
    counting = CountingEvaluator(lambda p: float(p["a"]))
    pts = [{"a": i, "b": 0} for i in range(3)]

    ex1 = EvaluationExecutor(counting, space, parallelism=2, cache_path=path)
    for p in ex1.as_completed(ex1.submit(pts)):
        assert p.result().value == pytest.approx(float(p.point["a"]))
    ex1.close()
    assert counting.calls == 3

    ex2 = EvaluationExecutor(counting, space, parallelism=2, cache_path=path)
    pend2 = ex2.submit(pts)
    assert all(p.done() for p in pend2)  # resolved straight from disk
    assert counting.calls == 3
    ex2.close()


# ---------------------------------------------------------------------------
# end to end: second tuning run hits the cache, 0 re-evaluations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,par", [("random", 1), ("exhaustive", 4)])
def test_second_tuning_run_zero_reevaluations(tmp_path, algo, par):
    path = str(tmp_path / "memo.json")
    counting = CountingEvaluator(lambda p: float(p["a"] * 10 + p["b"]))

    def run():
        t = Tuner(counting, small_space(),
                  TunerConfig(algorithm=algo, budget=10, seed=0,
                              verbose=False,
                              executor=ExecutorConfig(parallelism=par,
                                                      memo_cache_path=path)))
        h = t.run()
        t.close()
        return h

    h1 = run()
    first = counting.calls
    assert first == 10
    h2 = run()
    assert counting.calls == first  # disk memo: 0 re-evaluations
    assert sorted(e.value for e in h2.evals) == sorted(
        e.value for e in h1.evals)
    # cache hits are labeled so a run report can show what was reused
    assert all(e.meta.get("memoized") for e in h2.evals)


def test_executor_evaluate_batch_is_single_flush(tmp_path):
    """N evaluations that complete together persist as ONE store write
    (read-merge-write of the whole file per put is the O(N^2) pattern
    this kills)."""
    space = small_space()
    path = str(tmp_path / "memo.json")
    ex = EvaluationExecutor(lambda p: float(p["a"]), space, parallelism=4,
                            cache_path=path)
    evaluate_all(ex, [{"a": i, "b": 0} for i in range(8)])
    assert ex.cache.flushes == 1  # one put_many for the whole drain
    assert len(JsonCacheStore(path).load()) == 8
    ex.close()
    assert ex.cache.flushes == 1  # close had nothing left to write


def test_executor_async_drain_flushes_at_most_once_per_drain(tmp_path):
    """The completion-driven path batches too: each next_completed drain
    is at most one flush, and simultaneous completions share it."""
    space = small_space()
    path = str(tmp_path / "memo.json")
    ex = EvaluationExecutor(lambda p: float(p["a"]), space, parallelism=4,
                            cache_path=path)
    pend = ex.submit([{"a": i, "b": 1} for i in range(8)])
    drains = 0
    remaining = list(pend)
    while remaining:
        done = ex.next_completed(remaining)
        remaining.remove(done)
        drains += 1
    assert ex.cache.flushes <= drains  # never more writes than drains
    assert len(JsonCacheStore(path).load()) == 8  # nothing lost
    ex.close()


def test_serial_backend_still_persists_via_submit_flush(tmp_path):
    space = small_space()
    path = str(tmp_path / "memo.json")
    ex = EvaluationExecutor(lambda p: float(p["a"]), space, parallelism=1,
                            cache_path=path)
    ex.submit([{"a": i, "b": 2} for i in range(3)])
    assert ex.cache.flushes == 1  # the serial submit is one drain
    assert len(JsonCacheStore(path).load()) == 3
    ex.close()


def _contending_writer(path, wid, n_keys):
    store = JsonCacheStore(path)
    for i in range(n_keys):
        store.put(f"w{wid}-{i}", {"wid": wid, "i": i})
        store.put("shared", {"winner": wid})  # contested key


def test_cross_process_contention_loses_no_keys(tmp_path):
    """Two real processes hammering one store file: union across keys,
    a coherent parse, and last-writer-wins (one writer's intact record,
    never an interleaving) on the contested key."""
    path = tmp_path / "c.json"
    procs = [multiprocessing.Process(target=_contending_writer,
                                     args=(path, wid, 10))
             for wid in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    data = json.loads(path.read_text())  # file is coherent JSON
    assert {k for k in data if k != "shared"} \
        == {f"w{wid}-{i}" for wid in range(2) for i in range(10)}
    assert data["shared"] in ({"winner": 0}, {"winner": 1})
    for wid in range(2):
        for i in range(10):
            assert data[f"w{wid}-{i}"] == {"wid": wid, "i": i}


def test_timeout_and_preempt_placeholders_never_reach_disk(tmp_path):
    """A -inf under this run's timeout, and a preempted-before-start
    placeholder, are run-local artifacts: the cross-run store must stay
    clean so a later run measures those points for real."""
    space = small_space()
    path = str(tmp_path / "memo.json")

    def objective(p):
        if p["a"] == 9:
            time.sleep(0.5)  # will blow the 0.1s timeout
        return float(p["a"])

    ex = EvaluationExecutor(objective, space, parallelism=1,
                            backend="thread", timeout=0.1, cache_path=path)
    slow, queued, fast = ex.submit(
        [{"a": 9, "b": 0}, {"a": 1, "b": 0}, {"a": 2, "b": 0}])
    assert ex.preempt(queued) == "cancelled"  # 1-wide pool: still queued
    done = []
    remaining = [slow, fast]
    while remaining:
        p = ex.next_completed(remaining)
        remaining.remove(p)
        done.append(p)
    by_a = {p.point["a"]: p.result() for p in done}
    assert by_a[9].meta.get("timeout") and by_a[9].value == -math.inf
    assert by_a[2].value == 2.0
    ex.close()
    stored = JsonCacheStore(path).load()
    stored_as = {json.loads(k)[0] for k in stored}
    assert stored_as == {2}  # the real measurement only: no 9, no 1
    # in-memory memo still knows the timeout for THIS run
    assert ex.cache.get(space.key({"a": 9, "b": 0})).meta.get("timeout")


def test_roofline_evaluator_reads_legacy_cache_format(tmp_path):
    """The store's on-disk format is the evaluator's historical plain-JSON
    dict, so pre-existing cache files keep working."""
    from repro.tuning.evaluator import RooflineEvaluator

    legacy = tmp_path / "tune_cache.json"
    legacy.write_text(json.dumps({"somekey": {"roofline": {"x": 1}}}))
    ev = RooflineEvaluator("qwen2-0.5b", "train_4k", cache_path=str(legacy))
    assert ev._cache == {"somekey": {"roofline": {"x": 1}}}
