"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel suite here) runs the kernel bodies but
skips the TPU compiler, and the TPU compiler is what refuses a block that
does not fit the ``(8, 128)`` tiling or a kernel that wants more VMEM
than it may use.  These tests compile each kernel at qwen2.5-3b serving
shapes for a ``v5e:2x2`` topology that is described, not attached
(``jax.experimental.topologies``), so such a refusal fails here and not
on the chip.  They also compile the model's decode and chunk step
programs whole and read the compiled program for copies of the KV pool,
which only the TPU compiler's layout choices show.  Nothing runs: a
compile that passes says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that describes it at
collection would make the others collect different tests.
"""

import contextlib
import functools
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.dist.sharding import activation_mesh, model_shardings
from repro.kernels import chunk_attention, led_matmul, paged_attention
from repro.launch.hlo_analysis import pallas_kernels
from repro.nn.attention import Attention, PagedKVCache

# qwen2.5-3b attention and MLP widths; the engine's block size and the
# slots / context / chunk width the chip smoke serves with
HEADS, KVH, HD = 16, 2, 128
D_MODEL, D_FF, RANK = 2048, 11008, 1024
BLOCK, SLOTS, MAX_LEN, CHUNK = 16, 8, 2048, 256
N_TABLE = MAX_LEN // BLOCK
N_BLOCKS = SLOTS * N_TABLE


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The yi-9b cell's mesh: ``data`` 1 x ``model`` 4 over the host."""
    return Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"))


def _compile(fn, kernel, *specs, sharding):
    """Compile ``fn`` at ``specs`` (shape, dtype) and check that the TPU
    program holds the Pallas kernel of the jitted wrapper ``kernel``."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert pallas_kernels(compiled.as_text()) == {kernel}


@pytest.mark.parametrize("pool_dtype,slots,n_table", [
    (jnp.float32, SLOTS, N_TABLE), (jnp.bfloat16, SLOTS, N_TABLE),
    # the chat benchmark's engine: 32 slots to 4,096 tokens
    (jnp.bfloat16, 32, 4096 // BLOCK)], ids=["f32", "bf16", "bf16-chat"])
def test_paged_attention_compiles(one_chip, pool_dtype, slots, n_table):
    pool = (KVH, slots * n_table, BLOCK, HD)
    _compile(functools.partial(paged_attention, interpret=False),
             "_paged_attention_call", ((slots, HEADS, HD), jnp.bfloat16),
             (pool, pool_dtype), (pool, pool_dtype),
             ((slots, n_table), jnp.int32),
             ((slots,), jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_chunk_attention_compiles(one_chip, pool_dtype):
    pool = (KVH, N_BLOCKS, BLOCK, HD)
    _compile(functools.partial(chunk_attention, interpret=False),
             "_chunk_attention_call", ((CHUNK, HEADS, HD), jnp.bfloat16),
             (pool, pool_dtype), (pool, pool_dtype), ((N_TABLE,), jnp.int32),
             ((CHUNK, KVH, HD), pool_dtype), ((CHUNK, KVH, HD), pool_dtype),
             ((), jnp.int32), ((), jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("rows", [SLOTS, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("d_in,d_out", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["up", "down"])
def test_led_matmul_compiles(one_chip, rows, d_in, d_out):
    _compile(functools.partial(led_matmul, interpret=False), "led_matmul_2d",
             ((rows, d_in), jnp.bfloat16), ((d_in, RANK), jnp.bfloat16),
             ((RANK, d_out), jnp.bfloat16), sharding=one_chip)


# yi-9b's attention at tensor parallelism 4: 32 query and 4 kv heads of 128
# over hidden 4096, so each chip's kernels see 8 query heads and 1 kv head;
# the chat cell's 32 slots to 4,096 tokens in blocks of 16
YI_D, YI_HEADS, YI_KVH, YI_SLOTS, YI_TABLE = 4096, 32, 4, 32, 4096 // BLOCK


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The attention layer's kernels compiled, not interpreted, while a
    test lowers it here (the program picks interpret mode by platform)."""
    for name in ("paged_attention", "chunk_attention", "pool_write"):
        monkeypatch.setattr(importlib.import_module(f"repro.kernels.{name}"),
                            "default_interpret", lambda: False)


@pytest.mark.parametrize("role", ["decode", "chunk"])
def test_shard_local_kernels_compile(four_chips, compiled_kernels, role):
    """The kernels, as the yi-9b cell runs them: under ``shard_map`` on a
    1 x 4 mesh, each chip's kernels at its own 8 query heads and 1 kv
    head of the whole stacked pool (bf16, 32 slots, 256 table entries,
    layer 1 of 2): the attention kernel and the in-place row write."""
    attn = jax.eval_shape(lambda k: Attention.create(
        k, YI_D, YI_HEADS, YI_KVH, head_dim=HD, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, shardings)

    def spec(shape, dtype, *dims):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
            four_chips, PartitionSpec(*dims)))

    attn = placed(attn, model_shardings(attn, four_chips))
    layers, n_blocks = 2, YI_SLOTS * YI_TABLE
    pool = spec((layers, YI_KVH, n_blocks, BLOCK, HD), jnp.bfloat16, None,
                "model")
    table = spec((YI_SLOTS, YI_TABLE), jnp.int32)
    length = spec((YI_SLOTS,), jnp.int32)
    cache = PagedKVCache(pool, pool, table, length)
    layer = spec((), jnp.int32)
    if role == "decode":
        def step(attn, x, cache, layer):
            return attn.decode(x, cache, decode_kernel="pallas", layer=layer)

        x = spec((YI_SLOTS, 1, YI_D), jnp.bfloat16)
        args, kernel = (attn, x, cache, layer), "_paged_attention_call"
        local = f"bf16[{YI_SLOTS},1,8,{HD}]"
    else:
        def step(attn, x, cache, layer, slot, dst):
            return attn.prefill_chunk(x, cache, slot=slot, offset=slot,
                                      n_valid=slot, dst=dst,
                                      prefill_kernel="pallas", layer=layer)

        x = spec((1, CHUNK, YI_D), jnp.bfloat16)
        args = (attn, x, cache, layer, spec((), jnp.int32),
                spec((CHUNK,), jnp.int32))
        kernel, local = "_chunk_attention_call", f"bf16[1,{CHUNK * 8},{HD}]"
    with activation_mesh(four_chips):
        text = jax.jit(step).lower(*args).compile().as_text()
    assert pallas_kernels(text) == {kernel, "_pool_write_call"}
    local_pool = f"bf16[{layers},1,{n_blocks},{BLOCK},{HD}]"
    calls = [ln.split(" = ", 1)[1] for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert calls and all(
        (local_pool if "_pool_write_call" in c else local) in c.split(" ")[0]
        for c in calls), calls


# -- the step programs keep the pool in place ---------------------------------

#: ops that move no data: a pool-sized result of one of these is the pool
#: buffer itself, handed along
_NO_COPY = {"parameter", "get-tuple-element", "bitcast", "tuple",
            "custom-call"}
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _pool_sized(text: str, pool_elems: int, n_layers: int) -> list:
    """Instructions outside the kernels whose result holds as many
    elements as the pool or as one layer of it (a copy, slice, re-tiling
    or update of it, in whatever shape)."""
    sizes = {pool_elems, pool_elems // n_layers}
    out = []
    for ln in text.splitlines():
        m = _INSTR.match(ln)
        if m and m.group(3) not in _NO_COPY and math.prod(
                int(d) for d in m.group(2).split(",") if d) in sizes:
            out.append(ln.strip()[:160])
    return out


@pytest.mark.parametrize("role", ["decode", "chunk"])
@pytest.mark.parametrize("where", ["one_chip", "mesh_1x4"])
def test_step_program_keeps_pool_in_place(topo, four_chips,
                                          compiled_kernels, where, role):
    """``TransformerLM.decode`` and ``prefill_chunk`` with the Pallas
    kernels and the pool donated, as the engine's step programs run them:
    at qwen2.5-3b widths on one chip and at yi-9b's on the 1 x 4 mesh (4
    layers, 32 slots, 8,192 blocks of 16, bf16).  The pool rides whole
    from the argument to the result: no instruction outside the kernels
    has a pool- or layer-sized result, and the program's temporaries are
    under 1/16 of the pool (a layer scan that slices the pool out and
    stacks it back needs several pools of them)."""
    from repro.configs import get_config
    from repro.dist.sharding import cache_shardings
    from repro.models import build_model

    layers, slots, n_blocks = 4, 32, 8192
    if where == "one_chip":
        cfg, mesh = get_config("qwen2.5-3b").replace(n_layers=layers), None
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        cfg, mesh = get_config("yi-9b").replace(n_layers=layers), four_chips
        sharding = NamedSharding(mesh, PartitionSpec())
    model = jax.eval_shape(lambda k: build_model(k, cfg),
                           jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        slots, n_blocks // slots * BLOCK, cfg, n_blocks=n_blocks,
        block_size=BLOCK, dtype=jnp.bfloat16))
    if mesh is None:
        model_sh = jax.tree_util.tree_map(lambda _: sharding, model)
        cache_sh = jax.tree_util.tree_map(lambda _: sharding, cache)
    else:
        model_sh = model_shardings(model, mesh)
        cache_sh = cache_shardings(cache, mesh)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, shardings)

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    def in_mesh():
        return (activation_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())

    if role == "decode":
        def step(model, tok, cache):
            with in_mesh():
                return model.decode(tok, cache, decode_kernel="pallas")

        rest = (spec((slots, 1)),)
    else:
        def step(model, toks, cache, slot, offset, n_valid, dst):
            with in_mesh():
                return model.prefill_chunk(
                    toks, cache, slot=slot, offset=offset, n_valid=n_valid,
                    dst=dst, prefill_kernel="pallas")

        rest = (spec((1, CHUNK)),)
    args = (placed(model, model_sh), *rest, placed(cache, cache_sh))
    if role == "chunk":
        args += (spec(()), spec(()), spec(()), spec((CHUNK,)))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    kernel = ("_paged_attention_call" if role == "decode"
              else "_chunk_attention_call")
    assert pallas_kernels(text) == {kernel, "_pool_write_call"}
    pool_elems = cache.k.size // (1 if mesh is None else mesh.size)
    assert _pool_sized(text, pool_elems, layers) == []
    pool_bytes = pool_elems * cache.k.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 16, (temp, pool_bytes)
