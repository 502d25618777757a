"""The controls, at a size a test run can hold: the float32 reference put
in the program's place at the precision below the one each cell states
(float8 operands for the bfloat16 train cells, bfloat16 for the tune cell's
float32) must come out not correct under the cell's own limits, and so must
half of each batch left out."""
import json

import jax
import pytest

from bench import harness, reference
from bench_fixtures import REPO, tiny_root, tiny_tune_root


def _limits(cell):
    return json.loads((REPO / "bench" / "limits" / f"{cell}.json").read_text())


def _fails(gaps, limits):
    return any(gaps[k] > limits[k] for k in limits if k in gaps)


@pytest.mark.parametrize("cell", ["qwen2-0.5b.train-2x1024",
                                  "h2o-danube-1.8b.train-2x2048"])
@pytest.mark.parametrize("variant", ["fp8", "half_batch"])
def test_train_controls_fail(tmp_path, cell, variant):
    c = harness.resolve(cell, 2**31 + 1, tiny_root(tmp_path))
    train = harness.load_driver("train")
    numerics, fault = ("f32", variant) if variant == "half_batch" else (variant, None)
    gaps = train.program_readings_vs(numerics, c, fault)
    assert _fails(gaps, _limits(cell)), gaps


def test_tune_control_fails(tmp_path):
    cell = "qwen2-0.5b.tune-attn"
    c = harness.resolve(cell, 2**31 + 1, tiny_tune_root(tmp_path))
    tune = harness.load_driver("tune")
    q, k, v = tune.Driver(c)._inputs()
    with jax.default_matmul_precision("highest"):
        ref = reference.attention(q, k, v, dot=reference.make_dot("f32"))
        low = reference.attention(q, k, v, dot=reference.make_dot("bf16"))
    assert _fails(tune.attention_errors(low, ref), _limits(cell))
