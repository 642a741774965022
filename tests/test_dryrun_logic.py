"""Unit tests for the dry-run machinery that don't need 512 devices:
shape/skip logic, input specs, sharding rules, roofline math."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ASSIGNED, get, param_count


def test_skip_logic_matches_design():
    from repro.launch.dryrun import shape_skip_reason
    runnable = {a: [] for a in ASSIGNED}
    for a in ASSIGNED:
        cfg = get(a)
        for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            if shape_skip_reason(cfg, s) is None:
                runnable[a].append(s)
    # ssm + hybrid keep long_500k; everyone else drops exactly that one
    assert "long_500k" in runnable["rwkv6_7b"]
    assert "long_500k" in runnable["jamba_1_5_large_398b"]
    for a in ASSIGNED:
        if a in ("rwkv6_7b", "jamba_1_5_large_398b"):
            assert len(runnable[a]) == 4
        else:
            assert len(runnable[a]) == 3
    # 32 runnable cells + 8 documented skips = the 40-cell matrix
    assert sum(len(v) for v in runnable.values()) == 32


@pytest.mark.parametrize("arch", ASSIGNED)
def test_input_specs_complete(arch):
    from repro.launch.dryrun import SHAPES, input_specs, shape_skip_reason
    cfg = get(arch)
    for shape in SHAPES:
        if shape_skip_reason(cfg, shape):
            continue
        ins = input_specs(cfg, shape)
        assert "tokens" in ins
        assert ins["tokens"].dtype == jnp.int32
        if cfg.family == "encdec":
            assert "frames" in ins        # stubbed modality frontend
        if cfg.family == "vlm":
            assert "patches" in ins


def test_param_counts_sane():
    """Sanity-pin the assigned configs against their public names."""
    total, active = param_count(get("kimi_k2_1t_a32b"))
    assert 0.9e12 < total < 1.2e12          # ~1T
    assert 25e9 < active < 40e9             # a32b
    total, _ = param_count(get("grok_1_314b"))
    assert 250e9 < total < 360e9
    total, _ = param_count(get("granite_34b"))
    assert 30e9 < total < 50e9
    total, _ = param_count(get("phi4_mini_3_8b"))
    assert 3e9 < total < 5.5e9
    total, _ = param_count(get("rwkv6_7b"))
    assert 5e9 < total < 9e9
    total, _ = param_count(get("jamba_1_5_large_398b"))
    assert 330e9 < total < 450e9


def test_param_spec_rules():
    if jax.device_count() < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count")
    from repro.launch.mesh import param_spec
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get("kimi_k2_1t_a32b")
    # experts: EP over model when divisible
    s = param_spec("groups/s1_moe/w_gate", (384, 7168, 2048), cfg, mesh,
                   fsdp=True)
    assert s[0] == "model" and s[1] == "data"
    # attention: column-parallel
    s = param_spec("groups/s0_attn/wq", (7168, 7168), cfg, mesh, fsdp=True)
    assert s[1] == "model"
    # contraction-side mats: row-parallel
    s = param_spec("groups/s0_attn/wo", (7168, 7168), cfg, mesh, fsdp=True)
    assert s[0] == "model"
    # vectors replicate
    assert param_spec("groups/s0_attn/ln", (7168,), cfg, mesh, True) == P(None)
    # embedding: vocab on model
    s = param_spec("embed", (163840, 7168), cfg, mesh, fsdp=True)
    assert s[0] == "model"


def test_roofline_analysis_math():
    from repro.launch.roofline import analyze
    rec = {
        "arch": "x", "shape": "train_4k", "n_devices": 256,
        "flops": 197e12,            # exactly 1 s of compute per chip
        "bytes_accessed": 819e9,    # exactly 1 s of HBM per chip
        "collective_bytes": {"total": 100e9},  # 2 s of ICI
        "params_active": 1e9,
    }
    r = analyze(rec)
    assert abs(r.compute_s - 1.0) < 1e-6
    assert abs(r.memory_s - 1.0) < 1e-6
    assert abs(r.collective_s - 2.0) < 1e-6
    assert r.dominant == "collective"
    assert r.step_time_s == r.collective_s
    # MODEL_FLOPS = 6 * 1e9 * (256*4096) tokens
    assert abs(r.model_flops - 6e9 * 256 * 4096) / r.model_flops < 1e-9


def test_collective_parser_handles_tuples():
    from repro.launch.hlo_cost import analyze_hlo
    hlo = """
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %ar = f32[8,8]{1,0} all-reduce(%p0), replica_groups={}
  ROOT %r = f32[8,8]{1,0} add(%ar, %ar)
}
"""
    out = analyze_hlo(hlo)
    assert out["all-reduce"] == 8 * 8 * 4
