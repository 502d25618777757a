"""The float32 reference against the program run in float32, at a tiny
size on the CPU: same weights from the seed, same batches, same training."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench_fixtures import tiny_root


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("ref"))
    c = harness.resolve("qwen2-0.5b.train-2x1024", 2**31 + 77, root)
    c.traffic = dict(c.traffic, attn_impl="ref", dtype="f32")
    return c


def test_weights_from_the_seed_match_the_program(cell):
    from repro.models.model import build_model
    from repro.models.params import split_params

    drv = harness.load_driver("train")
    prog, _ = split_params(build_model(drv.model_config(cell.config)).init(
        jax.random.PRNGKey(cell.seed)))
    ref = reference.init_params(cell.config, cell.seed)
    assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_batches_match_the_program(cell):
    from repro.data.pipeline import DataConfig, SyntheticTokens

    t = cell.traffic
    prog = SyntheticTokens(DataConfig(vocab_size=cell.config["vocab_size"],
                                      seq_len=t["seq_len"], global_batch=t["batch"],
                                      seed=cell.seed, noise=t["noise"]))
    for step in range(3):
        ref = reference.token_batch(vocab=cell.config["vocab_size"],
                                    seq_len=t["seq_len"], batch=t["batch"],
                                    seed=cell.seed, step=step, noise=t["noise"])
        for k in ("tokens", "targets"):
            np.testing.assert_array_equal(prog.batch_at(step)[k], ref[k])


def test_float32_program_agrees_with_the_reference(cell):
    drv = harness.load_driver("train").Driver(cell)
    drv.setup()
    drv.release()
    got = {k: v["value"] for k, v in drv.check().items()}
    # both sides compute in float32: only summation order differs
    assert got["loss_gap"] < 1e-5, got
    assert got["grad_gap"] < 1e-3, got
    assert got["change_gap"] < 1e-2, got


def test_attention_reference_is_causal_softmax():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 8, 4, 16)) for i in range(3))
    k, v = k[:, :, :2], v[:, :, :2]
    out = reference.attention(q, k, v, dot=reference.make_dot("f32"), rows=4)
    kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((8, 8), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
