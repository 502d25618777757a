"""Config plumbing: stray-point-key rejection, the shared remat enum,
and the every-remat-mode-lowers pin."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.models.runtime import REMAT_MODES, Runtime
from repro.tuning.parameters import (
    BackendConfig,
    _REMAT,
    backend_space,
    config_from_point,
)


def test_stray_point_key_raises_with_names():
    with pytest.raises(ValueError) as e:
        config_from_point({"log2_dp": 2, "blok_q": 256})
    assert "blok_q" in str(e.value)


def test_allow_extra_escape_hatch():
    bc = config_from_point({"log2_dp": 2, "host_devices": 4},
                           allow_extra=("host_devices",))
    assert bc.log2_dp == 2
    # allow_extra whitelists exactly the named keys, nothing else
    with pytest.raises(ValueError, match="other"):
        config_from_point({"other": 1}, allow_extra=("host_devices",))


def test_backend_space_points_always_construct():
    # every dim the search space can emit is a real BackendConfig field
    import numpy as np

    from repro.configs import get_config
    from repro.core.space import SearchSpace

    rng = np.random.default_rng(0)
    for arch in ("qwen2-0.5b", "rwkv6-3b"):
        space = SearchSpace.from_dicts(backend_space(get_config(arch)))
        for point in space.sample(rng, 3):
            config_from_point(point)


def test_remat_enum_is_shared_and_validated():
    assert _REMAT is REMAT_MODES
    assert "names" in REMAT_MODES  # the mode the old docstring dropped
    with pytest.raises(ValueError, match="remat"):
        BackendConfig(remat="nmaes")
    with pytest.raises(ValueError, match="remat"):
        Runtime(remat="checkpoint_dots")
    for mode in REMAT_MODES:  # every valid choice constructs both
        assert BackendConfig(remat=mode).runtime().remat == mode


@pytest.mark.parametrize("mode", REMAT_MODES)
def test_every_remat_mode_lowers(mode):
    """The drift bug in reverse: a mode the tuner can emit must lower."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.models.params import split_params
    from repro.optim.optimizer import OptimizerConfig, adamw_init
    from repro.train.train_step import make_train_step

    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    rt = dataclasses.replace(Runtime(compute_dtype="f32"), remat=mode)
    params, _ = split_params(model.init(jax.random.PRNGKey(0)))
    opt_cfg = OptimizerConfig(learning_rate=1e-3, warmup_steps=1,
                              total_steps=2)
    opt_state = adamw_init(params, opt_cfg)
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "targets": jnp.zeros((1, 16), jnp.int32)}
    step = make_train_step(model, opt_cfg, rt)
    jax.jit(step).lower(params, opt_state, batch)  # must not raise
