"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel suite here) runs the kernel bodies but
skips the TPU compiler, and the TPU compiler is what refuses a block that
does not fit the ``(8, 128)`` tiling or a kernel that wants more VMEM
than it may use.  These tests compile each kernel at qwen2.5-3b serving
shapes for a ``v5e:2x2`` topology that is described, not attached
(``jax.experimental.topologies``), so such a refusal fails here and not
on the chip.  Nothing runs: a compile that passes says nothing about
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that describes it at
collection would make the others collect different tests.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chunk_attention, led_matmul, paged_attention
from repro.launch.hlo_analysis import pallas_kernels

# qwen2.5-3b attention and MLP widths; the engine's block size and the
# slots / context / chunk width the chip smoke serves with
HEADS, KVH, HD = 16, 2, 128
D_MODEL, D_FF, RANK = 2048, 11008, 1024
BLOCK, SLOTS, MAX_LEN, CHUNK = 16, 8, 2048, 256
N_TABLE = MAX_LEN // BLOCK
N_BLOCKS = SLOTS * N_TABLE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
