"""The train loop on a 2x2 ``fsdp_tp`` mesh against one device, the flash
forward per shard, the collectives counter and the CLI's ``--mesh``: one
process on four virtual CPU devices (``tests/mesh_checks.py``), judged
here."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _one_core(pick):
    """The helper process on one core (the lowest or highest allowed) at a
    lower priority: no more load than the worker that waits for it, so the
    parallel run's timed windows keep their share of the CPU."""
    os.nice(10)
    os.sched_setaffinity(0, {pick(os.sched_getaffinity(0))})


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, os.path.join(REPO, "tests", "mesh_checks.py")],
                         env=env, capture_output=True, text=True, timeout=600,
                         preexec_fn=lambda: _one_core(min))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_trainer_on_mesh_matches_one_device(readings):
    one, mesh = readings["one"], readings["mesh"]
    # both sides compute in float32; the mesh only reorders sums (partial
    # products over split matrices, the gradient's reduction over data)
    np.testing.assert_allclose(mesh["loss"], one["loss"], rtol=1e-5)
    assert set(mesh["grad"]) == set(one["grad"])
    for name, g in one["grad"].items():
        assert mesh["grad"][name] == pytest.approx(g, rel=1e-4), name
    # Adam moves an element by about the learning rate (1e-3) a step; one
    # whose gradient is near zero can turn on reduction-order noise, so the
    # parameters after three steps agree to a tenth of one step
    for name, p in one["params"].items():
        np.testing.assert_allclose(mesh["params"][name], p, rtol=0, atol=1e-4,
                                   err_msg=name)


def test_no_matrix_or_its_moments_is_whole_on_a_device(readings):
    assert readings["mesh"]["whole_at_init"] == []
    assert readings["mesh"]["whole_after"] == []


def test_rollback_on_the_mesh_restores_into_the_same_shardings(readings):
    r, one = readings["rollback"], readings["one"]
    assert "failure at step 2" in r["events"] and "restored step 2" in r["events"]
    # the restored run replays the same stream: its three losses are the
    # uninterrupted run's
    np.testing.assert_allclose(r["loss"], one["loss"], rtol=1e-5)
    assert r["whole"] == [] and r["same_shardings"]


def test_pallas_attention_per_shard_matches_the_unsharded_call(readings):
    a = readings["attention"]
    assert a["shard_map_on_mesh"] and not a["shard_map_alone"]
    # the same kernel on the same rows and heads: only the split differs
    assert a["fwd_err"] <= 1e-6 * a["fwd_scale"]
    for err, scale in zip(a["grad_err"], a["grad_scale"]):
        assert err <= 1e-5 * scale


def test_step_collectives_counted_on_the_mesh_only(readings):
    assert readings["one"]["collectives"] == {}
    coll = readings["mesh"]["collectives"]
    assert coll and all(v > 0 for v in coll.values())
    assert "all-gather" in coll  # fsdp: the weights are gathered over data


def test_cli_trains_on_a_mesh(readings):
    c = readings["cli"]
    assert len(c["losses"]) == 2 and all(np.isfinite(c["losses"]))
    assert c["collectives"]
