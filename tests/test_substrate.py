"""Substrate tests: data determinism, checkpointing, fault tolerance,
gradient compression, optimizer, schedules, HLO analyzer, compile-cache
placement."""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import checkpointing as ckpt
from repro.data.pipeline import ProteinSampler, ShardInfo, SyntheticLM
from repro.optim import adamw, grad_compress
from repro.optim.schedule import warmup_cosine
from repro.runtime.fault_tolerance import (DriverConfig, StragglerWatch,
                                           TrainingDriver)

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------
def test_data_deterministic():
    a = SyntheticLM(128, 16, 8, seed=3).batch(5)
    b = SyntheticLM(128, 16, 8, seed=3).batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(128, 16, 8, seed=4).batch(5)
    assert not np.array_equal(a["tokens"], c["tokens"])


@given(st.sampled_from([1, 2, 4, 8]), st.integers(0, 10))
def test_data_shards_partition_global_batch(world, step):
    """Union of shard batches == the single-host global batch, in order."""
    full = SyntheticLM(128, 16, 8, seed=0).batch(step)
    parts = [SyntheticLM(128, 16, 8, seed=0,
                         shard=ShardInfo(r, world)).batch(step)
             for r in range(world)]
    np.testing.assert_array_equal(
        np.concatenate([p["tokens"] for p in parts]), full["tokens"])


def test_labels_are_shifted_tokens():
    b = SyntheticLM(128, 16, 4, seed=0).batch(0)
    # labels[t] is the next token of tokens[t] (same underlying stream)
    assert b["tokens"].shape == b["labels"].shape == (4, 16)
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_protein_sampler_lengths_and_determinism():
    s = ProteinSampler(seed=1, min_len=32, max_len=256)
    a, b = s.sample(7), s.sample(7)
    np.testing.assert_array_equal(a, b)
    assert 32 <= len(a) <= 256
    assert a.max() < 21


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------
def _tree(key):
    return {"w": jax.random.normal(key, (8, 4)),
            "nested": {"b": jnp.arange(5, dtype=jnp.int32)},
            "step": jnp.asarray(3)}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree(jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path), 12, tree)
    step, restored = ckpt.restore(str(tmp_path), tree)
    assert step == 12
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, restored)


def test_checkpoint_retention_and_latest(tmp_path):
    tree = _tree(jax.random.PRNGKey(0))
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, tree, keep_last_k=2)
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_00000004", "step_00000005"]
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_checkpoint_no_tmp_left_behind(tmp_path):
    ckpt.save(str(tmp_path), 1, _tree(jax.random.PRNGKey(0)))
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


def test_async_checkpointer(tmp_path):
    saver = ckpt.AsyncCheckpointer(str(tmp_path))
    tree = _tree(jax.random.PRNGKey(1))
    saver.save_async(7, tree)
    saver.wait()
    step, restored = ckpt.restore(str(tmp_path), tree)
    assert step == 7


# --------------------------------------------------------------------------
# fault tolerance
# --------------------------------------------------------------------------
def _counter_driver(tmp_path, fail_at=None, total=20):
    def step_fn(state, step):
        return {"x": state["x"] + step}, {"x": float(state["x"])}

    def init_fn():
        return {"x": jnp.zeros((), jnp.int32)}

    cfg = DriverConfig(total_steps=total, ckpt_every=5,
                       ckpt_dir=str(tmp_path), fail_at_step=fail_at)
    return TrainingDriver(cfg, step_fn, init_fn)


def test_driver_resume_equals_uninterrupted(tmp_path):
    clean = _counter_driver(tmp_path / "clean")
    s1 = clean.run()
    failed = _counter_driver(tmp_path / "failed", fail_at=13)
    s2 = failed.run()
    assert failed.restarts == 1
    assert int(s1["x"]) == int(s2["x"])          # bitwise-equal final state


def test_straggler_watch_flags_outlier():
    w = StragglerWatch(window=16, z_threshold=4.0)
    for i in range(20):
        w.observe(i, 0.1 + 0.001 * (i % 3))
    assert not w.flagged
    assert w.observe(20, 5.0)
    assert w.flagged == [20]


# --------------------------------------------------------------------------
# optimizer + schedules + grad compression
# --------------------------------------------------------------------------
def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=0.5, weight_decay=0.0, clip_norm=100.0)
    for _ in range(60):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, m = adamw.update(params, grads, state, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.3


def test_adamw_clipping():
    params = {"w": jnp.ones((4,))}
    state = adamw.init(params)
    grads = {"w": jnp.full((4,), 1e6)}
    _, _, m = adamw.update(params, grads, state,
                           adamw.AdamWConfig(clip_norm=1.0))
    assert float(m["grad_norm"]) > 1e5          # reported pre-clip


def test_schedule_monotone_warmup():
    vals = [float(warmup_cosine(jnp.asarray(s), warmup=10, total=100))
            for s in range(10)]
    assert all(b >= a for a, b in zip(vals, vals[1:]))


def test_grad_compress_error_feedback_unbiased():
    """Sum of quantized grads + final residual == sum of true grads."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 32)) * 0.1}
    state = grad_compress.init_state(g)
    total_sent = jnp.zeros_like(g["w"])
    for i in range(8):
        sent, state = grad_compress.compress_decompress(g, state, bits=8)
        total_sent = total_sent + sent["w"]
    true_total = 8 * g["w"]
    resid = state["w"]
    np.testing.assert_allclose(np.asarray(total_sent + resid),
                               np.asarray(true_total), rtol=1e-4, atol=1e-4)


def test_grad_compress_wire_bytes():
    g = {"w": jnp.zeros((16, 32))}
    assert grad_compress.wire_bytes(g, bits=8) == 16 * 32 + 16 * 4


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code (JAX
    reads the variable itself); otherwise the cache is the fixed,
    gitignored ``.jax_cache`` at the checkout root."""
    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(cc.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(cc.ENV, raising=False)
    try:
        got = cc.place_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
