"""Training infrastructure: optimizer, checkpoint roundtrip + crash
consistency, data determinism, compression, resilience, end-to-end loss
decrease, restart equivalence."""
import json
import math
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import get_model
from repro.train import AdamWConfig, checkpoint as ck, make_train_step
from repro.train.data import Prefetcher, SyntheticLM
from repro.train.optimizer import adamw_update, cosine_lr, init_opt_state
from repro.train.resilience import RunGuard, StepMonitor, replan_mesh


def test_adamw_quadratic_convergence():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=200, min_lr_ratio=1.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = init_opt_state(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(cfg, params, grads, opt)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(cosine_lr(cfg, jnp.int32(s))) for s in (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0 and lrs[1] == pytest.approx(0.5, abs=0.01)
    assert lrs[2] == pytest.approx(1.0, abs=0.01)
    assert lrs[-1] == pytest.approx(0.1, abs=0.01)


def test_grad_clip():
    cfg = AdamWConfig(lr=0.0, clip_norm=1.0)
    params = {"w": jnp.zeros(3)}
    opt = init_opt_state(params)
    _, _, stats = adamw_update(cfg, params, {"w": jnp.asarray([3.0, 4.0, 0.0])},
                               opt)
    assert float(stats["grad_norm"]) == pytest.approx(5.0)


def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"a": np.arange(6, dtype=np.float32).reshape(2, 3)},
             "opt": {"step": np.int32(7)}}
    ck.save(tmp_path, 7, state)
    restored, step = ck.restore(tmp_path, state)
    assert step == 7
    np.testing.assert_array_equal(restored["params"]["a"],
                                  state["params"]["a"])


def test_checkpoint_crash_consistency(tmp_path):
    """A checkpoint without MANIFEST.json must be invisible."""
    state = {"w": np.ones(3, np.float32)}
    ck.save(tmp_path, 1, state)
    # fake a crashed save at step 2: shard present, no manifest
    d = tmp_path / "step_00000002"
    d.mkdir()
    np.savez(d / "shard_0.npz", w=np.zeros(3, np.float32))
    assert ck.latest_step(tmp_path) == 1
    restored, step = ck.restore(tmp_path, state)
    assert step == 1 and restored["w"][0] == 1.0


def test_checkpointer_async_and_gc(tmp_path):
    c = ck.Checkpointer(tmp_path, keep=2)
    state = {"w": np.ones(2, np.float32)}
    for s in (10, 20, 30):
        c.save_async(s, state)
    c.wait()
    steps = sorted(int(d.name.split("_")[1]) for d in tmp_path.iterdir()
                   if d.name.startswith("step_"))
    assert steps == [20, 30]


def test_data_determinism_and_prefetch():
    d1 = SyntheticLM(vocab=100, seq_len=16, global_batch=4, seed=9)
    d2 = SyntheticLM(vocab=100, seq_len=16, global_batch=4, seed=9)
    b1, b2 = d1.batch_at(3), d2.batch_at(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    it = Prefetcher(iter([d1.batch_at(i) for i in range(3)]))
    got = list(it)
    assert len(got) == 3


def test_host_sharding_partition():
    full = SyntheticLM(vocab=50, seq_len=8, global_batch=8, seed=1)
    h0 = SyntheticLM(vocab=50, seq_len=8, global_batch=8, num_hosts=2,
                     host_id=0, seed=1)
    assert h0.batch == 4 and full.batch == 8


def test_compression_error_feedback():
    """int8 EF compression: single-device psum == near-identity with
    residual carrying the quantization error."""
    from repro.train.compression import compressed_psum_tree, init_residuals
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(64),
                          jnp.float32)}
    r = init_residuals(g)

    def f(g, r):
        return compressed_psum_tree(g, r, "pod")

    out, new_r = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=P()))(g, r)
    # compressed value + residual == original (error feedback identity)
    np.testing.assert_allclose(np.asarray(out["w"] + new_r["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-6)


def test_replan_mesh():
    m = replan_mesh(1, prefer_model=16)
    assert dict(m.shape) == {"data": 1, "model": 1}


def test_step_monitor_straggler():
    mon = StepMonitor(alpha=1.0, straggler_factor=1.5)
    mon.start(); mon.ema = 0.001
    import time
    time.sleep(0.01)
    t = mon.finish()
    assert t["straggler_alarm"]


def test_runguard_nan_rollback(tmp_path):
    g = RunGuard(None, interval=10, max_rollbacks=1)
    assert g.check_loss(1.0)
    assert not g.check_loss(float("nan"))
    with pytest.raises(RuntimeError):
        g.check_loss(float("nan"))


@pytest.mark.slow
def test_end_to_end_training_loss_decreases(tmp_path):
    """Integration: 60 steps on the reduced internlm2; loss must drop."""
    from repro.launch.train import main
    losses = main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "60",
                   "--batch", "4", "--seq", "64", "--ckpt-dir",
                   str(tmp_path / "ck"), "--log-every", "30"])
    assert losses[-1] < losses[0] - 0.3


@pytest.mark.slow
def test_restart_equivalence(tmp_path):
    """Kill-and-restart: resuming from the checkpoint reproduces the same
    final loss as an uninterrupted run (same data stream)."""
    from repro.launch.train import main
    ck1 = str(tmp_path / "a")
    full = main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "40",
                 "--batch", "4", "--seq", "64", "--ckpt-dir", ck1,
                 "--ckpt-every", "20", "--log-every", "100"])
    ck2 = str(tmp_path / "b")
    part = main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "40",
                 "--batch", "4", "--seq", "64", "--ckpt-dir", ck2,
                 "--ckpt-every", "20", "--log-every", "100",
                 "--abort-after", "25"])
    resumed = main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "40",
                    "--batch", "4", "--seq", "64", "--ckpt-dir", ck2,
                    "--resume", "--ckpt-every", "100", "--log-every", "100"])
    assert resumed[-1] == pytest.approx(full[-1], rel=1e-3)


def test_serve_generate():
    from repro.launch.serve import main
    out = main(["--arch", "internlm2-1.8b", "--reduced", "--batch", "2",
                "--prompt-len", "8", "--gen", "4"])
    assert out.shape == (2, 4)


def test_replan_mesh_after_failures():
    """Elastic re-mesh: losing devices still yields a valid (data, model)
    factorization, preferring to keep the TP degree."""
    for n, want in ((16, (4, 4)), (12, (3, 4)), (6, (3, 2)), (5, (5, 1))):
        m = replan_mesh(n, prefer_model=4, devices=list(range(n)))
        assert dict(m.shape) == {"data": want[0], "model": want[1]}, (n, m)
