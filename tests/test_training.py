"""Training substrate: loss decreases, checkpoint/restore exact resume,
NaN watchdog, ZeRO-1 state shardings, compressed psum."""
import os
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.training import (AdamWConfig, SyntheticLM, Trainer, TrainerConfig,
                            compressed_psum)


def make_trainer(tmp, arch="granite-3-2b", **tkw):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    adamw = AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=200)
    tcfg = TrainerConfig(ckpt_dir=str(tmp), ckpt_every=5, micro_batches=2,
                         **tkw)
    return model, Trainer(model, adamw, tcfg)


def test_loss_decreases(tmp_path):
    model, tr = make_trainer(tmp_path)
    params, state = tr.init_state(0)
    data = SyntheticLM(model.cfg.vocab_size, seq_len=32, global_batch=8,
                       mode="markov")
    params, state, hist = tr.run(params, state, data, num_steps=30)
    assert np.mean(hist[-5:]) < np.mean(hist[:5]) - 0.3, hist[:5] + hist[-5:]


def test_checkpoint_exact_resume(tmp_path):
    model, tr = make_trainer(tmp_path)
    params, state = tr.init_state(0)
    data = SyntheticLM(model.cfg.vocab_size, seq_len=32, global_batch=8)
    params, state, hist = tr.run(params, state, data, num_steps=12)
    # fresh trainer restores step 10 and reproduces steps 10-11 exactly
    model2, tr2 = make_trainer(tmp_path)
    p2, s2, meta = tr2.restore(10)
    p2, s2, hist2 = tr2.run(p2, s2, data, num_steps=12, start_step=10)
    assert np.allclose(hist[-2:], hist2, rtol=1e-5), (hist[-2:], hist2)


def test_nan_watchdog_restores(tmp_path):
    model, tr = make_trainer(tmp_path)
    params, state = tr.init_state(0)
    data = SyntheticLM(model.cfg.vocab_size, seq_len=32, global_batch=8)
    params, state, _ = tr.run(params, state, data, num_steps=10)
    # poison params -> next step NaN -> watchdog must restore from step 10
    bad = jax.tree.map(lambda x: x * jnp.nan, params)
    p2, s2, hist = tr.run(bad, state, data, num_steps=12, start_step=10)
    assert all(np.isfinite(hist)), hist
    assert tr.restores >= 1


def test_zero1_shardings_cover_params(tmp_path):
    model, tr = make_trainer(tmp_path, zero1=True)
    flat = jax.tree.leaves(tr.opt_shardings.mu)
    assert len(flat) == len(jax.tree.leaves(model.struct()))


def test_compressed_psum_error_feedback():
    mesh = jax.make_mesh((1,), ("d",), devices=jax.devices()[:1])
    x = jnp.linspace(-3, 3, 64, dtype=jnp.float32)

    def body(x):
        total, err = compressed_psum(x, "d")
        return total, err

    total, err = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(jax.sharding.PartitionSpec("d"),),
        out_specs=(jax.sharding.PartitionSpec("d"),) * 2))(x)
    # quantization error is carried, not lost
    assert np.allclose(np.asarray(total) + np.asarray(err), np.asarray(x),
                       atol=1e-6)
