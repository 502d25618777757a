"""Compile rehearsals for the TPU v5e, and the guards that keep the
program from hiding the device.

The kernel tests compile each Pallas kernel of the main path at the
widths the models call it with, for a v5e chip that is described, not
attached: Mosaic's tiling, layout and VMEM refusals show up here, which
interpret mode on the CPU cannot see.  Only a worker that runs this file
loads the TPU compiler, and it does so inside the fixture.
"""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one described v5e chip.  The persistent compilation
    cache is off meanwhile: entries compiled for a described chip cannot
    be read back without one."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    own_log_dir = "TPU_LOG_DIR" not in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if own_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


def _kernel_call(name):
    """(fn, [(shape, dtype)]) of one kernel at a main-path width:
    qwen2-0.5b for attention and norm (train 2 x 1024, serve 8 requests
    with a 544-slot cache), h2o-danube-1.8b for the flash backward, jamba
    for the selective scan, rwkv6-3b for the wkv scan."""
    import jax.numpy as jnp

    from repro.kernels import (decode_attention, flash_attention, gla_scan,
                               rmsnorm, ssm_scan)

    bf, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":
        return (lambda q, k, v: flash_attention.flash_attention(q, k, v),
                [((2, 1024, 14, 64), bf), ((2, 1024, 2, 64), bf),
                 ((2, 1024, 2, 64), bf)])
    if name == "flash_attention_bwd":
        # h2o-danube-1.8b: head_dim 80, GQA 32/8, the window, train 2 x 2048
        return (lambda *a: flash_attention.flash_attention_bwd(
                    *a, window=4096, block_q=512, block_kv=512),
                [((2, 2048, 32, 80), bf), ((2, 2048, 8, 80), bf),
                 ((2, 2048, 8, 80), bf), ((2, 2048, 32, 80), bf),
                 ((2, 2048, 32), f32), ((2, 2048, 32, 80), bf)])
    if name == "decode_attention":
        return (lambda q, k, v, n: decode_attention.decode_attention(q, k, v, n),
                [((8, 14, 64), bf), ((8, 544, 2, 64), bf),
                 ((8, 544, 2, 64), bf), ((8,), jnp.int32)])
    if name == "rmsnorm":
        return (lambda x, s: rmsnorm.rmsnorm(x, s),
                [((2048, 896), bf), ((896,), f32)])
    if name == "ssm_scan":
        return (lambda *a: ssm_scan.ssm_scan(*a),
                [((1, 512, 8192), bf), ((1, 512, 8192), bf), ((8192, 16), f32),
                 ((1, 512, 16), bf), ((1, 512, 16), bf), ((8192,), f32)])
    assert name == "gla_scan"
    return (lambda *a: gla_scan.gla_scan(*a),
            [((1, 512, 40, 64), bf)] * 4 + [((40, 64), f32)])


@pytest.mark.parametrize("name", ["flash_attention", "flash_attention_bwd",
                                  "decode_attention", "rmsnorm", "ssm_scan",
                                  "gla_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax

    fn, args = _kernel_call(name)
    structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------


def test_interpret_mode_only_on_cpu(monkeypatch):
    import jax

    from repro.kernels import ops

    assert ops._interpret()  # the CPU backend interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ops._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    """On the CPU backend, in the checkout or copied alone into an empty
    directory, the smoke exits non-zero and never reports success."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path))
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []  # JAX read the variable itself; no other dir set


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    import jax

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
