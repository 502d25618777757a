"""Sharding rules + HLO analysis units, and a subprocess mini dry-run."""
import os
import subprocess
import sys

import pytest

from repro.tuning.hlo_analysis import (
    collect_collective_stats,
    shape_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_shape_bytes():
    assert shape_bytes("f32[8,256]{1,0}") == 8 * 256 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[4], s32[2,2])") == 16 + 16
    assert shape_bytes("token[]") == 0


HLO_SAMPLE = """
HloModule test

%body (p: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %ar = f32[8,64]{1,0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[], f32[8,64]) tuple(%i, %ar)
}

ENTRY %main (a: f32[16,64]) -> f32[16,64] {
  %ag = f32[16,64]{1,0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[8,64]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %out = f32[16,64]{1,0} copy(%ag)
}
"""


def test_collective_stats_scales_while_bodies():
    stats = collect_collective_stats(HLO_SAMPLE)
    assert stats.count_by_kind["all-gather"] == 1
    assert stats.count_by_kind["all-reduce"] == 4  # trip count applied
    assert stats.bytes_by_kind["all-reduce"] == 4 * 8 * 64 * 4
    assert stats.bytes_by_kind["all-gather"] == 16 * 64 * 4


#: the TPU compiler's form: no known_trip_count on the while, collectives
#: inside called computations, one all-gather copied into the fusions that
#: start and continue it (one channel), a reduce-scatter as an all-reduce
#: inside an ``all-reduce-scatter`` computation
HLO_TPU_SAMPLE = """
HloModule test

%cond (p: (s32[], f32[8,64])) -> pred[] {
  %c = s32[]{:T(128)} constant(24)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%ag_start (a: f32[4,64]) -> f32[8,64] {
  ROOT %ag.1 = f32[8,64]{1,0} all-gather(%a), channel_id=7, dimensions={0}
}

%async_collective_fusion.1 (a: f32[4,64]) -> f32[8,64] {
  ROOT %ag.2 = f32[8,64]{1,0} all-gather(%a), channel_id=7, dimensions={0}
}

%all-reduce-scatter.3 (g: f32[8,64]) -> f32[4,64] {
  %ar = f32[8,64]{1,0} all-reduce(%g), channel_id=9, to_apply=%add
  ROOT %ds = f32[4,64]{1,0} dynamic-slice(%ar, %o, %z), dynamic_slice_sizes={4,64}
}

%body (p: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %s = f32[8,64]{1,0} fusion(%w), kind=kCustom, calls=%ag_start
  %f = f32[8,64]{1,0} fusion(%w), kind=kCustom, calls=%async_collective_fusion.1
  %rs = f32[4,64]{1,0} fusion(%f), kind=kCustom, calls=%all-reduce-scatter.3
  ROOT %t = (s32[], f32[8,64]) tuple(%i, %f)
}

ENTRY %main (a: f32[16,64]) -> f32[16,64] {
  %w = (s32[], f32[8,64]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[16,64]{1,0} copy(%a)
}
"""


def test_collective_stats_reads_the_tpu_compilers_form():
    stats = collect_collective_stats(HLO_TPU_SAMPLE)
    assert dict(stats.count_by_kind) == {"all-gather": 24, "reduce-scatter": 24}
    assert stats.bytes_by_kind["all-gather"] == 24 * 8 * 64 * 4
    assert stats.bytes_by_kind["reduce-scatter"] == 24 * 8 * 64 * 4


def test_sharding_rules_divisibility():
    """Rules drop axes whose dims don't divide the mesh axis size."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import ShardingRules

    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    rules = ShardingRules.__new__(ShardingRules)
    rules.mesh = FakeMesh()
    rules.style = "fsdp_tp"
    from repro.distributed.sharding import make_rules

    rules.rules = make_rules("fsdp_tp", multi_pod=False)
    # 14 heads on 16-way model axis: dropped; ff 4864 divides: kept
    spec = rules.spec_for(("embed", "heads", None), (896, 14, 64))
    assert spec == P("data")  # heads dropped, embed kept (fsdp)
    spec2 = rules.spec_for(("embed", "ff"), (896, 4864))
    assert spec2 == P("data", "model")
    # conflicting axes: first dim wins the mesh axis
    spec3 = rules.spec_for(("ff", "ff"), (4864, 4864))
    assert spec3 == P("model")


def test_backend_space_adapts_per_arch():
    """Attention-free archs drop attention tiles (paper's per-model ranges)."""
    from repro.configs import get_config
    from repro.tuning.parameters import backend_space

    rwkv_dims = {d["name"] for d in backend_space(get_config("rwkv6-3b"))}
    dense_dims = {d["name"] for d in backend_space(get_config("qwen2-0.5b"))}
    moe_dims = {d["name"] for d in backend_space(get_config("qwen3-moe-30b-a3b"))}
    assert "block_q" not in rwkv_dims and "scan_chunk" in rwkv_dims
    assert "block_q" in dense_dims and "capacity_factor" not in dense_dims
    assert "capacity_factor" in moe_dims


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Real lower+compile through the dryrun CLI on a tiny placeholder mesh."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-0.5b",
         "--shape", "decode_32k", "--chips-per-pod", "16", "--log2-dp", "2"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert "OK" in out.stdout, out.stdout + out.stderr


def test_roofline_math():
    from repro.tuning.cost_model import Roofline

    r = Roofline(flops_per_device=197e12 * 0.01, bytes_per_device=819e9 * 0.02,
                 collective_bytes=50e9 * 0.005, tokens_per_step=1000,
                 chips=256, model_flops=197e12 * 0.01 * 256 * 0.5,
                 memory_per_device=8e9)
    assert r.bottleneck == "memory"
    assert abs(r.est_step_time - 0.02) < 1e-9
    assert abs(r.throughput - 1000 / 0.02) < 1e-6
    assert r.fits_hbm is True
    assert abs(r.roofline_fraction - 0.5) < 1e-9
    assert abs(r.mfu - 0.25) < 1e-9
