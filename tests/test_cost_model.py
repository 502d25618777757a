"""Cost-model units: analytic traffic, kernel credit, backend config."""

import pytest

from repro.configs import get_config, get_shape
from repro.tuning.cost_model import (
    analytic_hbm_traffic,
    kernel_traffic_bytes,
    model_flops,
    tokens_per_step,
)
from repro.tuning.hlo_analysis import traffic_analysis
from repro.tuning.parameters import BASELINE, BackendConfig, config_from_point


def test_backend_config_mesh_factorization():
    bc = BackendConfig(log2_dp=4)
    assert bc.dp() == 16 and bc.tp() == 16 and bc.dp() * bc.tp() == 256
    bc2 = BackendConfig(log2_dp=8)
    assert bc2.dp() == 256 and bc2.tp() == 1
    bc3 = BackendConfig(log2_dp=0)
    assert bc3.dp() == 1 and bc3.tp() == 256


def test_config_from_point_roundtrip():
    pt = {"log2_dp": 2, "remat": "names", "microbatches": 4, "block_q": 256}
    bc = config_from_point(pt)
    assert bc.log2_dp == 2 and bc.remat == "names" and bc.microbatches == 4
    assert bc.block_q == 256
    # a stray key (typo'd search-space dim) must be loud, not silently
    # dropped; allow_extra is the explicit opt-out for keys a harness
    # handles outside BackendConfig
    with pytest.raises(ValueError, match="not_a_field"):
        config_from_point(dict(pt, not_a_field=1))
    bc2 = config_from_point(dict(pt, not_a_field=1),
                            allow_extra=("not_a_field",))
    assert bc2 == bc


def test_model_flops_conventions():
    cfg = get_config("qwen2-0.5b")
    n = cfg.param_counts()["active"]
    tr = get_shape("train_4k")
    de = get_shape("decode_32k")
    assert model_flops(cfg, tr, n) == 6.0 * n * tr.global_batch * tr.seq_len
    assert model_flops(cfg, de, n) == 2.0 * n * de.global_batch
    assert tokens_per_step(de) == de.global_batch


def test_moe_active_params_lt_total():
    cfg = get_config("qwen3-moe-30b-a3b")
    pc = cfg.param_counts()
    assert pc["active"] < pc["total"] / 3  # 8 of 128 experts active
    dense = get_config("deepseek-coder-33b").param_counts()
    assert dense["active"] == dense["total"]
    # totals near the nameplate sizes
    assert 25e9 < cfg.param_counts()["total"] < 36e9
    assert 28e9 < dense["total"] < 38e9


def test_param_counts_sane_for_all_archs():
    expected = {
        "jamba-v0.1-52b": (45e9, 60e9),
        "grok-1-314b": (250e9, 360e9),
        "minicpm3-4b": (3e9, 6e9),
        "rwkv6-3b": (2.5e9, 4.5e9),
        "whisper-base": (0.05e9, 0.12e9),
        "h2o-danube-1.8b": (1.4e9, 2.4e9),
        "internvl2-26b": (17e9, 26e9),  # LM backbone only (vision stubbed)
        "qwen2-0.5b": (0.4e9, 0.8e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_counts()["total"]
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


def test_analytic_traffic_scales_with_shape():
    cfg = get_config("qwen2-0.5b")
    tr = analytic_hbm_traffic(cfg, get_shape("train_4k"), BASELINE, 256)
    de = analytic_hbm_traffic(cfg, get_shape("decode_32k"), BASELINE, 256)
    assert tr["total"] > de["total"]  # train moves far more bytes
    assert de["params"] > de["activations"]  # decode is weight/cache-bound
    for v in tr.values():
        assert v >= 0


def test_kernel_credit_decode_scales_with_cache():
    cfg = get_config("deepseek-coder-33b")
    k32 = kernel_traffic_bytes(cfg, get_shape("decode_32k"), BASELINE, 256)
    assert k32 > 0
    cfg_swa = get_config("h2o-danube-1.8b")
    k_long = kernel_traffic_bytes(cfg_swa, get_shape("long_500k"), BASELINE, 256)
    k_dec = kernel_traffic_bytes(cfg_swa, get_shape("decode_32k"), BASELINE, 256)
    # SWA bounds the cache: long context costs the same per token
    assert k_long <= k_dec * 1.01


def test_traffic_analysis_excludes_tagged_ops():
    hlo = '''
ENTRY %main (a: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  %m1 = f32[64,64]{1,0} multiply(%p, %p), metadata={op_name="jit(f)/krnl_flash_attn/mul"}
  %m2 = f32[64,64]{1,0} multiply(%m1, %m1), metadata={op_name="jit(f)/other/mul"}
  ROOT %c = f32[64,64]{1,0} copy(%m2)
}
'''
    st = traffic_analysis(hlo)
    per_op = 64 * 64 * 4
    assert st.excluded_bytes == 3 * per_op  # m1: out + 2 operands
    assert st.included_bytes == 3 * per_op + 2 * per_op  # m2 + copy
    assert "krnl_flash_attn" in st.excluded_by_tag


def test_traffic_analysis_scales_a_nested_loop_by_both_trip_counts():
    """A body runs its own trip count times its enclosing loop's."""
    hlo = '''
%inner (a: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  ROOT %m = f32[64,64]{1,0} multiply(%p, %p)
}

%outer (b: f32[64,64]) -> f32[64,64] {
  %q = f32[64,64]{1,0} parameter(0)
  ROOT %w = f32[64,64]{1,0} while(%q), condition=%c1, body=%inner, backend_config={"known_trip_count":{"n":"5"}}
}

ENTRY %main (x: f32[64,64]) -> f32[64,64] {
  %x = f32[64,64]{1,0} parameter(0)
  ROOT %w0 = f32[64,64]{1,0} while(%x), condition=%c0, body=%outer, backend_config={"known_trip_count":{"n":"3"}}
}
'''
    per_op = 64 * 64 * 4
    # the multiply (out + two operands) 15 times; parameters and whiles move nothing
    assert traffic_analysis(hlo).included_bytes == 15 * 3 * per_op
