"""Distributed semantics: the sharded train step must compute the SAME math
as single-device execution.  Runs in a subprocess with 8 forced host devices
(the XLA device count is locked at first jax init, so it cannot be set in
this process)."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.optim.adamw import AdamWState
    from repro.train import TrainState, make_train_step
    from repro.dist.sharding import (activation_mesh, data_sharding,
                                     model_shardings)

    cfg = get_config("paper-tiny").reduced().replace(
        dtype="float32", n_heads=4, n_kv_heads=4, d_model=64, head_dim=16)
    model = build_model(jax.random.PRNGKey(0), cfg)
    opt = AdamW(1e-2, master_fp32=False)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    step = make_train_step(opt)

    def fresh_state():
        return TrainState(model=model, opt=opt.init(model),
                          step=jnp.zeros((), jnp.int32))

    # --- single device (reference) ---
    ref_state, ref_metrics = jax.jit(step)(fresh_state(), batch)

    # --- sharded: dp=4 x tp=2 mesh, TP+FSDP+activation constraints ---
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ms = model_shardings(model, mesh, fsdp=True)
    repl = NamedSharding(mesh, P())
    st_sh = TrainState(model=ms, opt=AdamWState(step=repl, m=ms, v=ms,
                                                master=None), step=repl)
    b_sh = {k: data_sharding(mesh, v.shape) for k, v in batch.items()}
    with mesh, activation_mesh(mesh):
        sharded = jax.jit(step, in_shardings=(st_sh, b_sh))(
            fresh_state(), batch)
    sh_state, sh_metrics = sharded

    np.testing.assert_allclose(float(ref_metrics["loss"]),
                               float(sh_metrics["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(ref_metrics["grad_norm"]),
                               float(sh_metrics["grad_norm"]), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state.model),
                    jax.tree_util.tree_leaves(sh_state.model)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
    print("DISTRIBUTED_EQUIVALENCE_OK")
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "DISTRIBUTED_EQUIVALENCE_OK" in r.stdout
