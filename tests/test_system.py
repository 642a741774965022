"""End-to-end behaviour tests for the full system: train→checkpoint→restart
continuity, the serving engine, and the dry-run cell machinery on a small
in-process mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get, smoke
from repro.models.model import build_model
from repro.serve.engine import Engine, Request
from repro.train.trainer import TrainerConfig, train


def test_train_checkpoint_restart_continuity(tmp_path):
    """Crash-and-restart must resume from LATEST and keep improving."""
    cfg = smoke(get("stablelm_12b"))
    t1 = TrainerConfig(steps=20, ckpt_dir=str(tmp_path), ckpt_every=10,
                       global_batch=4, seq_len=32, peak_lr=2e-3, warmup=5)
    out1 = train(cfg, t1)
    # "crash" — new trainer restores from the final checkpoint
    t2 = TrainerConfig(steps=40, ckpt_dir=str(tmp_path), ckpt_every=10,
                       global_batch=4, seq_len=32, peak_lr=2e-3, warmup=5)
    out2 = train(cfg, t2)
    assert int(out2["state"].step) == 40
    assert out2["final_loss"] <= out1["final_loss"] + 0.05


def test_engine_serves_batches():
    cfg = smoke(get("granite_34b"))
    eng = Engine(cfg, slots=3, max_len=48)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6), max_new=5)
            for i in range(7)]
    results = eng.run(reqs)
    assert set(results) == set(range(7))
    assert all(len(v) == 5 for v in results.values())
    assert all(0 <= t < cfg.vocab for v in results.values() for t in v)


def test_decode_matches_prefill_logits():
    """Teacher-forced decode over a prompt reproduces prefill's last logits
    (KV-cache correctness end to end)."""
    cfg = smoke(get("mistral_nemo_12b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)

    full_logits, _ = model.prefill(params, toks, max_len=16)

    # token-by-token decode of the same prompt
    first, cache = model.prefill(params, toks[:, :1], max_len=16)
    logits = first
    for t in range(1, 8):
        logits, cache = model.decode_step(params, cache, toks[:, t:t + 1], t)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits), atol=2e-3, rtol=2e-3)


def test_dryrun_cell_small_mesh(tmp_path):
    """The dry-run machinery end to end on an in-process 2×2 mesh."""
    if jax.device_count() < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count")
    import repro.launch.mesh as mesh_mod
    from repro.launch.hlo_cost import analyze_hlo
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = smoke(get("phi4_mini_3_8b"))
    model = build_model(cfg)
    from repro.train.train_step import make_train_step
    init_state, train_step, _ = make_train_step(model)
    shapes = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
    sh = mesh_mod.shard_pytree_specs(shapes, cfg, mesh, fsdp=True)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    bsh = {"tokens": NamedSharding(mesh, P("data", None))}
    with jax.set_mesh(mesh):
        lowered = jax.jit(train_step, in_shardings=(sh, bsh),
                          out_shardings=(sh, None)).lower(shapes, batch)
        compiled = lowered.compile()
    cost = analyze_hlo(compiled.as_text())
    assert cost["dot_flops"] > 0
    assert cost["collective_total"] > 0  # TP/FSDP must communicate
