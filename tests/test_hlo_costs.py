"""Trip-count-aware HLO analysis: exact flop counts on known workloads."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_costs import analyze, parse_computations


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_plain_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    txt = _hlo(lambda a, b: a @ b, a, a)
    assert analyze(txt).flops == 2 * 512 ** 3


def test_batched_dot_flops_exact():
    ab = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    txt = _hlo(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), ab, ab)
    assert analyze(txt).flops == 4 * 2 * 64 ** 3


def test_scan_flops_scaled_by_trip_count():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    txt = _hlo(scanned, x, ws)
    assert analyze(txt).flops == 8 * 2 * 128 * 256 * 256


def test_collectives_in_scan_multiplied():
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("mp",))

    def fn(x, ws):
        def body(x, w):
            return jax.lax.psum(jnp.tanh(x @ w), "mp"), None
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    from jax import shard_map

    sm = shard_map(fn, mesh=mesh, in_specs=(P(), P()), out_specs=P())
    c = analyze(_hlo(sm, x, ws))
    assert c.collective_count.get("all-reduce") == 8
    assert c.collective_bytes["all-reduce"] == 8 * 128 * 256 * 4


def test_scan_weight_slicing_not_counted_as_full_reads():
    """The stacked weights are loop-invariant; per-iteration bytes must be
    ~one layer's slice, not the whole stack."""

    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((64, 256, 256), jnp.float32)
    c = analyze(_hlo(scanned, x, ws))
    full_stack = 64 * 256 * 256 * 4
    # 64 iterations x full-stack reads would be 64*full_stack; sliced reads
    # are ~1x full_stack total. Allow generous slack for copies.
    assert c.bytes < 8 * full_stack


def test_parse_computations_finds_entry():
    a = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    comps, entry = parse_computations(_hlo(lambda a: a @ a, a))
    assert entry is not None and entry in comps
    assert any(op.kind == "dot" for op in comps[entry].ops) or any(
        op.kind == "fusion" for op in comps[entry].ops)


def test_constrain_acts_noop_without_mesh():
    from repro.dist.sharding import constrain_acts

    x = jnp.ones((4, 8, 16))
    assert constrain_acts(x) is x
