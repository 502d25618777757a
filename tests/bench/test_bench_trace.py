"""The trace reduction: busy union, idle gaps named by host events, on
events built by hand and on a trace recorded on a TPU v5e."""
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]


def test_gaps_are_attributed_to_the_innermost_host_event():
    host = [("step", 0.0, 100.0), ("compile", 10.0, 20.0), ("feed", 60.0, 10.0)]
    gaps = [(0.0, 40.0), (55.0, 65.0), (95.0, 110.0)]
    got = trace.attribute(gaps, host)
    assert got == {"step": 10 + 10 + 5 + 5, "compile": 20, "feed": 5,
                   trace.NO_HOST: 10}


def test_summary_of_planes_built_by_hand():
    planes = [
        {"name": "/device:TPU:0", "lines": {
            "XLA Ops": [("fusion", 10.0, 20.0), ("dot", 25.0, 15.0), ("dot", 60.0, 20.0)],
            "XLA Modules": [("jit_step", 10.0, 70.0)]}},
        {"name": "/host:CPU", "lines": {"python3": [
            ("run", 0.0, 100.0), ("feed", 40.0, 20.0)]}},
    ]
    s = trace.summarize(planes, n_chips=1)
    assert s["busy_s"] == pytest.approx(50e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["device_ops"][0] == ["dot", pytest.approx(35e-9)]
    gaps = dict(s["idle_gaps"])
    assert gaps == {"run": pytest.approx(30e-9), "feed": pytest.approx(20e-9)}


def test_recorded_tpu_trace():
    files = sorted(DATA.glob("*.xplane.pb"))
    if not files:
        pytest.fail("the recorded trace is missing")
    s = trace.summarize(trace.load(files[0]), n_chips=1)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and s["idle_gaps"]
