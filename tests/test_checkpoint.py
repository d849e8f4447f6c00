"""Checkpoint manager: atomicity, resume, GC, structure guards."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.checkpoint import CheckpointManager


class State(nn.Module):
    w: jax.Array
    b: jax.Array


def make_state(v):
    return State(w=jnp.full((4, 4), float(v)), b=jnp.arange(3.0))


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    st = make_state(7)
    mgr.save(10, st)
    step, restored = mgr.restore_latest(make_state(0))
    assert step == 10
    np.testing.assert_allclose(np.asarray(restored.w), np.asarray(st.w))
    np.testing.assert_allclose(np.asarray(restored.b), np.asarray(st.b))


def test_latest_points_to_newest(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    for s in (5, 20, 15):
        mgr.save(s, make_state(s))
    assert mgr.latest_step() == 15  # LATEST tracks most recent SAVE


def test_keep_last_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in range(1, 6):
        mgr.save(s, make_state(s))
    assert mgr.all_steps() == [4, 5]


def test_partial_write_is_invisible(tmp_path):
    """A .tmp dir (simulated crash mid-save) must never be restored."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, make_state(1))
    os.makedirs(str(tmp_path / "step_00000002.tmp"))
    assert mgr.latest_step() == 1
    # also: a step dir without manifest is ignored
    os.makedirs(str(tmp_path / "step_00000003"))
    assert mgr.latest_step() == 1


def test_treedef_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, make_state(1))

    class Other(nn.Module):
        w: jax.Array

    with pytest.raises(ValueError):
        mgr.restore(1, Other(w=jnp.zeros((4, 4))))


def test_none_leaves_roundtrip(tmp_path):
    lin = nn.Linear.create(jax.random.PRNGKey(0), 4, 4, use_bias=False)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, lin)
    _, restored = mgr.restore_latest(
        nn.Linear.create(jax.random.PRNGKey(1), 4, 4, use_bias=False))
    assert restored.bias is None
    np.testing.assert_allclose(np.asarray(restored.weight),
                               np.asarray(lin.weight))


def test_restore_casts_to_template_dtype(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, State(w=jnp.ones((4, 4), jnp.float32), b=jnp.zeros(3)))
    _, restored = mgr.restore_latest(
        State(w=jnp.zeros((4, 4), jnp.bfloat16), b=jnp.zeros(3)))
    assert restored.w.dtype == jnp.bfloat16


def test_train_driver_resume(tmp_path, monkeypatch):
    """End-to-end: kill training mid-run, relaunch, confirm resume."""
    from repro.launch.train import main

    # a cache placed from outside is left alone, so the driver does not
    # switch on a persistent cache for the rest of this test process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    ckpt = str(tmp_path / "run")
    rc = main(["--arch", "paper-tiny", "--reduced", "--steps", "6",
               "--batch", "4", "--seq", "16", "--ckpt-dir", ckpt,
               "--ckpt-every", "3"])
    assert rc == 0
    mgr = CheckpointManager(ckpt)
    assert mgr.latest_step() == 6
    # a second invocation resumes (instantly: start == steps)
    rc = main(["--arch", "paper-tiny", "--reduced", "--steps", "6",
               "--batch", "4", "--seq", "16", "--ckpt-dir", ckpt])
    assert rc == 0
