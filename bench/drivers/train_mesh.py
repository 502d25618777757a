"""Train driver on a mesh: ``Trainer.run`` over several chips.

The train driver (``bench/drivers/train.py``) with two differences: the
``Trainer`` is built on a ``data`` x ``model`` mesh over the cell's chips
(the traffic file's ``mesh``, ``sharding_style`` and ``remat``), and the
check runs the float32 reference split over the same chips
(``bench/reference_sharded.py``), for a model whose float32 training state
no one chip holds. Set-up, the window, the readings and their comparison
are the train driver's.
"""
from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp

from bench import harness, reference, reference_sharded

_train = harness.load_driver("train", pathlib.Path(__file__).resolve().parents[1])
CHECK_STEPS = _train.CHECK_STEPS


def _batches(cell):
    t, m = cell.traffic, cell.config
    return [reference.token_batch(
        vocab=m["vocab_size"], seq_len=t["seq_len"], batch=t["batch"],
        seed=cell.seed, step=i, noise=t["noise"]) for i in range(CHECK_STEPS)]


class Driver(_train.Driver):
    def setup(self):
        from repro.data.pipeline import DataConfig
        from repro.launch.mesh import make_mesh
        from repro.models.runtime import Runtime
        from repro.optim.optimizer import OptimizerConfig
        from repro.train.trainer import Trainer, TrainerConfig

        cfg = _train.model_config(self.model)
        t = self.t
        self.devices = harness.accelerators(self.cell.chips)
        shape = (t["mesh"]["data"], t["mesh"]["model"])
        mesh = make_mesh(shape, ("data", "model"), self.devices)
        self.trainer = Trainer(
            cfg, OptimizerConfig(**t["optimizer"]),
            DataConfig(vocab_size=cfg.vocab_size, seq_len=t["seq_len"],
                       global_batch=t["batch"], seed=self.cell.seed,
                       noise=t["noise"]),
            TrainerConfig(steps=0, seed=self.cell.seed, log_every=0),
            rt=Runtime(attn_impl=t["attn_impl"], compute_dtype=t["dtype"],
                       remat=t["remat"]),
            mesh=mesh, sharding_style=t["sharding_style"])
        # the first parameters wait on the host, so no chip holds two copies
        with self._bookkeeping():
            p0 = jax.device_get(self.trainer.params)
        self._run_to(1)
        with self._bookkeeping():
            scale = 1.0 / (1 - t["optimizer"]["beta1"])
            norms = jax.jit(reference.leaf_norms)(self.trainer.opt_state["m"])
            grad = {k: v * scale for k, v in
                    reference.flat_norms(jax.device_get(norms)).items()}
        self._run_to(CHECK_STEPS)
        with self._bookkeeping():
            p0 = jax.device_put(p0, self.trainer.shardings.params)
            moved = jax.jit(lambda a, b: reference.leaf_norms(
                jax.tree_util.tree_map(jnp.subtract, a, b)))(self.trainer.params, p0)
            moved = reference.flat_norms(jax.device_get(moved))
            del p0
            self.readings = {
                "loss": [m["loss"] for m in self.trainer.metrics_log],
                "grad": grad, "change": moved}
        self.tokens_per_step = t["batch"] * t["seq_len"]

    def check(self) -> dict:
        ref = reference_sharded.lm_readings(self.model, self.t["optimizer"],
                                            _batches(self.cell), self.cell.seed,
                                            self.devices)
        gaps = _train.compare(self.readings, ref)
        self.counters["check"] = gaps.pop("_where")
        return {k: {"value": v, "limit": self.cell.limits[k]}
                for k, v in gaps.items()}


def program_readings_vs(ref_numerics: str, cell, fault=None, ref=None) -> dict:
    """The sharded reference put in the program's place at ``ref_numerics``
    (and an optional planted ``fault``), compared with the float32 sharded
    reference (``ref``, its readings where already computed) as the check
    compares the program's readings."""
    devices = harness.accelerators(cell.chips)
    t, m, batches = cell.traffic, cell.config, _batches(cell)
    if ref is None:
        ref = reference_sharded.lm_readings(m, t["optimizer"], batches, cell.seed,
                                            devices)
    other = reference_sharded.lm_readings(m, t["optimizer"], batches, cell.seed,
                                          devices, numerics=ref_numerics, fault=fault)
    gaps = _train.compare(other, ref)
    gaps.pop("_where")
    return gaps
