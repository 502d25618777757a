"""Checks of the four-chip cell at a tiny size, run as a separate process
on four virtual CPU devices (``tests/bench/test_bench_mesh.py`` starts it
with ``--xla_force_host_platform_device_count=4``). Prints one JSON object
of readings for the tests to judge.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src:.:tests/bench python tests/bench/mesh_bench_checks.py
"""
import contextlib
import io
import json
import pathlib
import shutil
import tempfile
import time
from unittest import mock

import jax

from bench import control_mesh, harness, reference, reference_sharded
from bench_fixtures import REPO, TINY_MODEL, _edit, tiny_root

CELL = "h2o-danube-1.8b-full.train-fsdp4"
#: four rows: the data axis and the reference's four chips each take a share
TINY_MESH_TRAIN = {"seq_len": 64, "batch": 4}


def mesh_root(tmp: pathlib.Path) -> pathlib.Path:
    root = tiny_root(tmp)
    _edit(root / "bench" / "configs" / "h2o-danube-1.8b-full.json", **TINY_MODEL)
    _edit(root / "bench" / "traffic" / "train-fsdp4.json", **TINY_MESH_TRAIN)
    return root


def references(cell, driver):
    """The split reference against the one-device reference, and the
    controls (fp8, half a batch) against the split reference."""
    t, m = cell.traffic, cell.config
    batches = driver._batches(cell)
    four = reference_sharded.lm_readings(m, t["optimizer"], batches, cell.seed,
                                         jax.devices()[:4])
    controls = {"fp8": driver.program_readings_vs("fp8", cell, ref=four),
                "half_batch": driver.program_readings_vs("f32", cell, "half_batch",
                                                         ref=four)}
    return {"one": reference.lm_readings(m, t["optimizer"], batches, cell.seed),
            "four": four}, controls


def fp8_run(root: pathlib.Path) -> dict:
    """A whole run of the cell under its own limits, with the split
    reference at float8 in the program's place: the program's readings
    become the float8 reference's once set-up has made them."""
    limits = f"{CELL}.json"
    shutil.copy(REPO / "bench" / "limits" / limits, root / "bench" / "limits" / limits)
    real = harness.load_driver

    def load_driver(kind, bench=harness.BENCH):
        mod = real(kind, bench)
        if kind == "train_mesh":
            class Driver(mod.Driver):
                def setup(self):
                    super().setup()
                    self.readings = reference_sharded.lm_readings(
                        self.model, self.t["optimizer"], mod._batches(self.cell),
                        self.cell.seed, self.devices, numerics="fp8")
            mod.Driver = Driver
        return mod

    with mock.patch.object(harness, "load_driver", load_driver):
        return harness.run(CELL, 2**31 + 25, 0.5, False, t_start=time.perf_counter(),
                           require_chip=False, root=root, log=lambda s: None)


def control_lines(root: pathlib.Path, *args: str) -> list:
    """``bench/control_mesh.py``'s printed lines for the cell."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        control_mesh.main(["--workload", CELL, *args], root=root)
    return [json.loads(line) for line in out.getvalue().splitlines()]


def main():
    assert len(jax.devices()) == 4, jax.devices()
    with tempfile.TemporaryDirectory() as tmp, \
            mock.patch.object(harness, "accelerators", lambda n: jax.devices()[:n]):
        root = mesh_root(pathlib.Path(tmp))
        cell = harness.resolve(CELL, 2**31 + 21, root)
        driver = harness.load_driver("train_mesh", root / "bench")
        out = dict(zip(("references", "controls"), references(cell, driver)))
        out["run"] = harness.run(CELL, 2**31 + 23, 0.5, False,
                                 t_start=time.perf_counter(), require_chip=False,
                                 root=root, log=lambda s: None)
        out["fp8_run"] = fp8_run(mesh_root(pathlib.Path(tmp) / "fp8"))
        seeds = [str(2**31 + 31 + i) for i in range(3)]
        out["control"] = control_lines(root, "--seeds", *seeds, "--variants",
                                       "program", "fp8", "half_batch")
        out["control_deadline"] = control_lines(root, "--seeds", *seeds[:2],
                                                "--variants", "program",
                                                "--deadline", "0")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
