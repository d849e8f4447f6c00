"""Sharding rules: Megatron TP + EP + LED boundary specs + FSDP fallbacks,
the paged/dense cache spec rules, and the activation-mesh context."""

import threading
from types import SimpleNamespace

import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (activation_mesh, active_activation_mesh,
                                 batch_spec, cache_specs, constrain_acts,
                                 spec_for_param)
from repro.nn.attention import KVCache, PagedKVCache


def mesh(shape_dict):
    return SimpleNamespace(shape=shape_dict)


POD = mesh({"data": 16, "model": 16})
MULTI = mesh({"pod": 2, "data": 16, "model": 16})
TP_ONLY = mesh({"model": 16})
DATA_ONLY = mesh({"data": 16})


def test_column_parallel_linear():
    assert spec_for_param("blocks.attn.q_proj.weight", (36, 2048, 2048),
                          POD) == P(None, None, "model")
    assert spec_for_param("blocks.mlp.up_proj.weight", (48, 4096, 11008),
                          POD) == P(None, None, "model")


def test_row_parallel_linear():
    assert spec_for_param("blocks.attn.o_proj.weight", (36, 2048, 2048),
                          POD) == P(None, "model", None)
    assert spec_for_param("blocks.mlp.down_proj.weight", (48, 11008, 4096),
                          POD) == P(None, "model", None)


def test_vocab_parallel_embedding_and_head():
    assert spec_for_param("embed.weight", (151936, 2048), POD) == \
        P("model", None)
    assert spec_for_param("lm_head.weight", (2048, 151936), POD) == \
        P(None, "model")


def test_column_bias_sharded_row_bias_replicated():
    assert spec_for_param("blocks.attn.q_proj.bias", (36, 2048), POD) == \
        P(None, "model")
    assert spec_for_param("blocks.mlp.down_proj.bias", (36, 4096), POD) == \
        P(None, None)


def test_led_factor_boundary_sharding():
    # column-parallel layer: A replicated, B out-sharded
    assert spec_for_param("blocks.attn.q_proj.A", (36, 2048, 128), POD) == \
        P(None, None, None)
    assert spec_for_param("blocks.attn.q_proj.B", (36, 128, 2048), POD) == \
        P(None, None, "model")
    # row-parallel layer: A in-sharded, B replicated
    assert spec_for_param("blocks.attn.o_proj.A", (36, 2048, 128), POD) == \
        P(None, "model", None)
    assert spec_for_param("blocks.attn.o_proj.B", (36, 128, 2048), POD) == \
        P(None, None, None)


def test_expert_parallel():
    # (L, E, in, out): expert axis on "model"
    assert spec_for_param("blocks.mlp.experts.gate_proj.weight",
                          (61, 384, 7168, 2048), POD) == \
        P(None, "model", None, None)
    # factorized experts keep EP
    assert spec_for_param("blocks.mlp.experts.up_proj.A",
                          (61, 384, 7168, 128), POD) == \
        P(None, "model", None, None)


def test_router_and_norms_replicated():
    assert spec_for_param("blocks.mlp.router.weight", (61, 7168, 384),
                          POD) == P(None, None, None)
    assert spec_for_param("blocks.attn_norm.scale", (36, 2048), POD) == \
        P(None, None)


def test_divisibility_fallback():
    # hymba vocab 32001 is not divisible by 16 → replicate that dim
    assert spec_for_param("lm_head.weight", (1600, 32001), POD) == \
        P(None, None)
    assert spec_for_param("embed.weight", (32001, 1600), POD) == \
        P(None, None)


def test_fsdp_adds_data_axis():
    spec = spec_for_param("blocks.mlp.experts.gate_proj.weight",
                          (61, 384, 7168, 2048), POD, fsdp=True)
    assert spec == P(None, "model", "data", None)
    # small params stay unsharded on data
    spec_small = spec_for_param("blocks.attn_norm.scale", (36, 2048), POD,
                                fsdp=True)
    assert spec_small == P(None, None)


def test_fsdp_multipod_uses_both_dp_axes():
    spec = spec_for_param("blocks.mlp.down_proj.weight",
                          (48, 11008, 4096), MULTI, fsdp=True)
    assert spec == P(None, "model", ("pod", "data"))


def test_batch_spec():
    assert batch_spec(POD) == P("data")
    assert batch_spec(MULTI) == P(("pod", "data"))


def test_mamba_projections():
    assert spec_for_param("blocks.mixer.in_proj.weight", (64, 2560, 10368),
                          POD) == P(None, None, "model")
    assert spec_for_param("blocks.mixer.out_proj.weight", (64, 5120, 2560),
                          POD) == P(None, "model", None)
    assert spec_for_param("blocks.mixer.A_log", (64, 80), POD) == P(None, None)


# -- replication-fallback spec matrix over mesh shapes -----------------------

# non-divisible dims must replicate NO MATTER the mesh shape; divisible
# dims shard only on the axes the mesh actually has
_MESHES = {"pod": POD, "multi": MULTI, "tp_only": TP_ONLY,
           "data_only": DATA_ONLY}


@pytest.mark.parametrize("name", sorted(_MESHES))
def test_fallback_matrix_odd_vocab_replicates(name):
    m = _MESHES[name]
    # 32001 % 16 != 0 → both the table and the head replicate everywhere
    assert spec_for_param("embed.weight", (32001, 1600), m) == P(None, None)
    assert spec_for_param("lm_head.weight", (1600, 32001), m) == \
        P(None, None)


@pytest.mark.parametrize("name", sorted(_MESHES))
def test_fallback_matrix_odd_proj_dims(name):
    m = _MESHES[name]
    has_tp = "model" in m.shape
    # divisible output dim shards iff the mesh has a model axis
    want = P(None, None, "model") if has_tp else P(None, None, None)
    assert spec_for_param("blocks.attn.q_proj.weight", (4, 64, 2048),
                          m) == want
    # odd output dim (prime) replicates even with a model axis
    assert spec_for_param("blocks.attn.q_proj.weight", (4, 64, 2003),
                          m) == P(None, None, None)
    # odd input dim on a row-parallel layer replicates too
    assert spec_for_param("blocks.attn.o_proj.weight", (4, 2003, 64),
                          m) == P(None, None, None)
    # odd expert count falls back from expert parallelism (the expert
    # branch owns the param: no silent downgrade to column sharding)
    assert spec_for_param("blocks.mlp.experts.up_proj.weight",
                          (4, 17, 64, 2048), m) == \
        P(None, None, None, None)


def test_fallback_matrix_fsdp_skips_odd_dims():
    # fsdp walks to the FIRST data-divisible free dim: dim 1 (11008) on
    # POD; a shape with no divisible free dim stays unsharded on data
    assert spec_for_param("blocks.mlp.down_proj.weight", (47, 11008, 4096),
                          POD, fsdp=True) == P(None, "model", "data")
    assert spec_for_param("blocks.mlp.router.weight", (47, 2003, 383),
                          POD, fsdp=True) == P(None, None, None)


# -- cache spec rules: paged pool vs dense per-slot lanes --------------------


def _leaf(*shape):
    return SimpleNamespace(shape=shape)


def test_paged_cache_specs():
    # pool (L, kvh, n_blocks, bs, hd): blocks GLOBAL over data (the host
    # allocator is placement-free), kv heads over "model"; table/length
    # shard their batch dim over data
    cache = PagedKVCache(k=_leaf(2, 16, 64, 8, 64), v=_leaf(2, 16, 64, 8, 64),
                         table=_leaf(32, 16), length=_leaf(2, 32))
    specs = cache_specs(cache, POD)
    assert specs.k == P(None, "model", None, None, None)
    assert specs.v == P(None, "model", None, None, None)
    assert specs.table == P("data", None)
    assert specs.length == P(None, "data")


def test_paged_cache_specs_gqa_fallback():
    # kv_heads=3 does not divide model=16 → pool replicates entirely
    cache = PagedKVCache(k=_leaf(2, 3, 64, 8, 64), v=_leaf(2, 3, 64, 8, 64),
                         table=_leaf(32, 16), length=_leaf(2, 32))
    specs = cache_specs(cache, POD)
    assert specs.k == P(None, None, None, None, None)
    assert specs.table == P("data", None)
    # odd batch → table and length replicate but heads still shard
    cache = PagedKVCache(k=_leaf(2, 16, 64, 8, 64), v=_leaf(2, 16, 64, 8, 64),
                         table=_leaf(33, 16), length=_leaf(2, 33))
    specs = cache_specs(cache, POD)
    assert specs.k == P(None, "model", None, None, None)
    assert specs.table == P(None, None)
    assert specs.length == P(None, None)


def test_dense_cache_specs():
    # per-slot lanes (L, B, S, kvh, hd): batch over data, heads over model
    cache = KVCache(k=_leaf(2, 32, 128, 16, 64), v=_leaf(2, 32, 128, 16, 64),
                    length=_leaf(2, 32))
    specs = cache_specs(cache, POD)
    assert specs.k == P(None, "data", None, "model", None)
    assert specs.length == P(None, "data")
    # multi-pod meshes spread the batch over both data axes
    specs = cache_specs(cache, MULTI)
    assert specs.k == P(None, ("pod", "data"), None, "model", None)


# -- activation_mesh context: thread-safe by construction --------------------


def _one_device_mesh():
    import jax
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_activation_mesh_does_not_leak_across_threads():
    # BackgroundServer traces engine steps off the main thread; a scope
    # entered on THIS thread must be invisible there (ContextVar — each
    # thread starts from a fresh context), so constrain_acts stays the
    # identity off-thread and an off-thread scope is invisible here
    m = _one_device_mesh()
    x = jnp.ones((4, 8))
    seen = {}
    inner = threading.Event()
    release = threading.Event()

    def probe():
        seen["off_thread_scope"] = active_activation_mesh()
        seen["off_thread_identity"] = constrain_acts(x) is x
        with activation_mesh(m, seq_parallel=True):
            inner.set()
            release.wait(timeout=10)

    with activation_mesh(m):
        assert active_activation_mesh() == (m, False)
        t = threading.Thread(target=probe)
        t.start()
        assert inner.wait(timeout=10)
        # the probe thread is INSIDE its own seq-parallel scope right now;
        # this thread still sees only its own
        assert active_activation_mesh() == (m, False)
        release.set()
        t.join()
    assert seen["off_thread_scope"] is None
    assert seen["off_thread_identity"]
    assert active_activation_mesh() is None


def test_activation_mesh_restores_on_exception():
    m = _one_device_mesh()
    x = jnp.ones((4, 8))
    with pytest.raises(RuntimeError):
        with activation_mesh(m):
            raise RuntimeError("boom")
    assert active_activation_mesh() is None
    assert constrain_acts(x) is x


def test_activation_mesh_scopes_nest():
    m = _one_device_mesh()
    with activation_mesh(m):
        with activation_mesh(m, seq_parallel=True):
            assert active_activation_mesh() == (m, True)
        assert active_activation_mesh() == (m, False)  # outer restored
    assert active_activation_mesh() is None
