"""Sharded serving: a ContinuousEngine on a {data, model} mesh must emit
BIT-IDENTICAL tokens to the single-device engine (and to one-shot
``generate``) on seeded traces — across dp-only / tp-only / dp x tp,
paged and dense layouts, chunked prefill with prefix reuse,
mid-prefill cancellation, and speculative decoding.

The differential matrix runs in subprocesses with 8 forced host devices
(the XLA device count is locked at first jax init, so it cannot be set
in this process); the runtime-config surface tests run in-process.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.dist.runtime import (HOST_DEVICES_RECIPE, RuntimeConfig,
                                make_serve_mesh, parse_mesh_spec)

# -- runtime config surface (no devices needed) ------------------------------


def test_parse_mesh_spec():
    assert parse_mesh_spec(None) is None
    assert parse_mesh_spec("") is None
    assert parse_mesh_spec("  ") is None
    assert parse_mesh_spec("2,2") == (2, 2)
    assert parse_mesh_spec(" 4 , 1 ") == (4, 1)
    assert parse_mesh_spec("4") == (4, 1)  # bare dp shorthand


@pytest.mark.parametrize("bad", ["0,2", "2,0", "-1,2", "a,b", "2,2,2", ","])
def test_parse_mesh_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_mesh_spec(bad)


def test_make_serve_mesh_empty_spec_is_single_device():
    assert make_serve_mesh("") is None


def test_make_serve_mesh_too_many_devices_names_the_recipe():
    # this process sees however many devices the environment exposes;
    # 64x64 exceeds any host, and the error must teach the CPU recipe
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_serve_mesh("64,64")


def test_runtime_config_env_seeding(monkeypatch):
    monkeypatch.setenv("REPRO_MESH", "2,4")
    monkeypatch.setenv("REPRO_SEQ_PARALLEL", "1")
    rc = RuntimeConfig()
    assert rc.mesh_spec == "2,4"
    assert rc.seq_parallel is True
    assert rc.fsdp_params is False
    assert set(rc.describe()) == {"mesh_spec", "seq_parallel", "fsdp_params"}
    assert "host_platform_device_count" in HOST_DEVICES_RECIPE


def test_single_device_kernels_run_unwrapped():
    """Off a mesh the engine's decode and chunk programs call both Pallas
    kernels directly: no ``shard_map`` and no collective."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ContinuousEngine

    cfg = get_config("yi-9b").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(model, cfg, batch=2, max_len=32,
                           max_prompt_len=16, kv_layout="paged",
                           block_size=8, chunk_size=8,
                           decode_kernel="pallas", prefill_kernel="pallas")
    assert eng.mesh is None
    decode = jax.make_jaxpr(eng._decode)(eng._models, eng.cache, eng.state,
                                         eng._next_key())
    i32 = jnp.int32
    chunk = jax.make_jaxpr(eng._chunk_mid)(
        eng._models, jnp.zeros((1, 8), i32), eng.cache, i32(0), i32(0),
        i32(8), jnp.zeros((8,), i32))
    for text in (str(decode), str(chunk)):
        assert "pallas_call" in text
        for prim in ("shard_map", "psum", "all_gather", "ppermute"):
            assert prim not in text, prim


# -- the sharded differential matrix (subprocess, 8 host devices) ------------

_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ContinuousEngine, bench_trace, make_trace
    from repro.serve.engine import generate
    from repro.dist import make_serve_mesh

    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    trace = make_trace(8, seed=0, load=0.5, min_prompt=4, max_prompt=12,
                       min_new=2, max_new=8, vocab=cfg.vocab,
                       shared_prefix=4)
    DIMS = dict(batch=4, max_len=48, max_prompt_len=16)
"""

MATRIX_SCRIPT = textwrap.dedent(_PRELUDE + """
    from jax.sharding import PartitionSpec as P

    # single-device references, both layouts
    ref = {}
    for layout in ("paged", "dense"):
        kw = dict(DIMS, kv_layout=layout)
        if layout == "paged":
            kw["block_size"] = 8
        rows, _ = bench_trace(model, cfg, trace, **kw)
        ref[layout] = {r.uid: tuple(r.tokens) for r in rows}

    # ... and one-shot generate agrees with the engine on each request
    for _t, req in trace[:3]:
        toks = jnp.asarray([req.prompt], jnp.int32)
        cache = model.init_cache(1, DIMS["max_len"], cfg)
        out, _ = generate(model, toks, cache, n_steps=req.max_new_tokens)
        want = list(ref["paged"][req.uid])
        got = [int(t) for t in np.asarray(out[0])][: len(want)]
        assert got == want, (req.uid, got, want)

    # mesh engines: dp-only, tp-only, dp x tp — bit-identical on both
    # layouts, with the intended NamedSharding on params / pool / state
    for spec in ("2,1", "1,2", "2,2"):
        mesh = make_serve_mesh(spec)
        dp, tp = mesh.shape["data"], mesh.shape["model"]
        for layout in ("paged", "dense"):
            kw = dict(DIMS, kv_layout=layout, mesh=mesh)
            if layout == "paged":
                kw["block_size"] = 8
            rows, _ = bench_trace(model, cfg, trace, **kw)
            got = {r.uid: tuple(r.tokens) for r in rows}
            assert got == ref[layout], (spec, layout)

        eng = ContinuousEngine(model, cfg, kv_layout="paged", block_size=8,
                               mesh=mesh, **DIMS)
        if tp > 1:
            assert eng.model.blocks.attn.q_proj.weight.sharding.spec \\
                == P(None, None, "model")
            assert eng.cache.k.sharding.spec \\
                == P(None, "model", None, None, None)
        if dp > 1:
            assert eng.cache.table.sharding.spec == P("data", None)
            assert eng.cache.length.sharding.spec == P(None, "data")
            assert eng.state.tok.sharding.spec == P("data")

    # the pallas kernels run shard-local on any mesh: each model shard
    # holds its kv heads' slice of the pool and no more
    for spec in ("1,2", "2,1"):
        mesh = make_serve_mesh(spec)
        eng = ContinuousEngine(model, cfg, kv_layout="paged", block_size=8,
                               mesh=mesh, decode_kernel="pallas",
                               prefill_kernel="pallas", **DIMS)
        st = eng.kv_stats()
        assert st["model_shards"] == mesh.shape["model"]
        assert st["pool_bytes_per_device"] * mesh.shape["model"] \
            == st["kv_allocated_bytes"], st

    # a model committed to a mesh brings it: no mesh argument, same engine
    mesh = make_serve_mesh("2,2")
    from repro.dist.sharding import model_shardings
    placed = jax.device_put(model, model_shardings(model, mesh))
    eng = ContinuousEngine(placed, cfg, kv_layout="paged", block_size=8,
                           **DIMS)
    assert eng.mesh.shape == mesh.shape
    assert eng.cache.k.sharding.spec == P(None, "model", None, None, None)
    assert eng.kv_stats()["model_shards"] == 2
    assert ContinuousEngine(model, cfg, **DIMS).mesh is None
    print("SHARDED_MATRIX_OK")
""")

CANCEL_SPEC_SCRIPT = textwrap.dedent(_PRELUDE + """
    from repro.core import auto_fact, spectral_decay

    mesh = make_serve_mesh("2,2")

    # cancellation mid-prefill leaks nothing under a mesh
    eng = ContinuousEngine(model, cfg, batch=2, max_len=64,
                           max_prompt_len=33, kv_layout="paged",
                           block_size=8, chunk_size=8, mesh=mesh)
    uid = eng.submit(list(range(1, 30)), max_new_tokens=4)
    keep = eng.submit([5, 6, 7, 8], max_new_tokens=4)
    eng.step()  # admits both; the long prompt is mid-prefill
    assert uid in [t.req.uid for t in eng._prefills.values()]
    eng.cancel(uid)
    done = eng.step()
    assert any(c.uid == uid and c.finish_reason == "cancelled"
               for c in done)
    out = list(done)
    for _ in range(20):
        out += eng.step()
        if eng.scheduler.idle:
            break
    assert any(c.uid == keep and c.finish_reason != "cancelled"
               for c in out)  # the survivor still completes
    assert eng.scheduler.idle and eng.manager.fully_free

    # speculative decoding: draft + verifier on the same mesh, greedy
    # agreement stays 1.0 vs the unsharded spec engine AND the plain
    # (non-speculative) unsharded engine
    smodel = spectral_decay(build_model(jax.random.PRNGKey(0), cfg), 2.5,
                            exclude=["embed", "lm_head"])
    draft = auto_fact(smodel, 0.25, solver="svd",
                      key=jax.random.PRNGKey(1),
                      exclude=["embed", "lm_head"], gate=False)
    kw = dict(DIMS, kv_layout="paged", block_size=8)
    plain, _ = bench_trace(smodel, cfg, trace, **kw)
    spec, sstats = bench_trace(smodel, cfg, trace, draft_model=draft,
                               spec_k=3, **kw)
    mspec, mstats = bench_trace(smodel, cfg, trace, draft_model=draft,
                                spec_k=3, mesh=mesh, **kw)
    t = lambda rows: {r.uid: tuple(r.tokens) for r in rows}
    assert t(mspec) == t(spec) == t(plain)
    assert mstats["spec_acceptance_rate"] == sstats["spec_acceptance_rate"]
    assert mstats["spec_drafted_tokens"] > 0
    print("SHARDED_CANCEL_SPEC_OK")
""")


# a llama-shaped model (yi-9b reduced: 8:1 GQA over 4 kv heads, an untied
# head, no bias) served with both Pallas kernels, interpreted on the CPU,
# under chat-like traffic replayed on a fixed step schedule: requests
# share two system prompts of two blocks each (prefix hits, one same-step
# provider the dependent must wait for, one prompt that hits whole),
# prompts and answers end on page boundaries, ten requests take turns on
# four slots, and two urgent arrivals preempt running decodes that later
# resume as prefix hits.  Every mesh must emit the single-device tokens.
PALLAS_SCRIPT = textwrap.dedent(_PRELUDE + """
    import sys
    from repro.serve import Request

    cfg = get_config("yi-9b").reduced().replace(n_heads=32, n_kv_heads=4,
                                                head_dim=8)
    model = build_model(jax.random.PRNGKey(1), cfg)
    assert model.lm_head is not None and cfg.n_heads // cfg.n_kv_heads == 8
    BS = 8
    rng = np.random.default_rng(7)
    systems = [rng.integers(0, cfg.vocab, 2 * BS) for _ in range(2)]
    # (step, system, user tokens, answer tokens, priority)
    PLAN = [(0, 0, 8, 12, 2), (0, 0, 5, 11, 2), (0, 1, 0, 9, 2),
            (0, 1, 3, 13, 2), (3, 0, 16, 6, 0), (5, 1, 7, 8, 1),
            (6, 0, 0, 5, 2), (9, 1, 8, 8, 2), (12, 0, 1, 7, 2),
            (14, 1, 15, 9, 2)]
    PROMPTS = [np.concatenate([systems[s], rng.integers(0, cfg.vocab, u)])
               .astype(np.int32) for _, s, u, _, _ in PLAN]

    def serve(mesh):
        eng = ContinuousEngine(model, cfg, batch=4, max_len=64,
                               max_prompt_len=48, kv_layout="paged",
                               block_size=BS, chunk_size=8,
                               decode_kernel="pallas",
                               prefill_kernel="pallas", mesh=mesh)
        uids, done, step = [], [], 0
        while step <= PLAN[-1][0] or not eng.scheduler.idle:
            for i, (at, _, _, new, prio) in enumerate(PLAN):
                if at == step:
                    uids.append(eng.submit(Request(
                        prompt=PROMPTS[i], max_new_tokens=new,
                        priority=prio)))
            done += eng.step()
            step += 1
            assert step < 500
        got = {c.uid: tuple(c.tokens) for c in done}
        return [got[u] for u in uids], eng.preempt_stats(), eng.kv_stats()

    want, pre, _ = serve(None)
    assert pre["preemptions"] >= 2 and pre["resumes"] >= 2, pre
    assert [len(t) for t in want] == [p[3] for p in PLAN]
    mesh = make_serve_mesh(sys.argv[1])
    got, mpre, kv = serve(mesh)
    assert got == want, (got, want)
    assert mpre == pre, (mpre, pre)
    assert kv["model_shards"] == mesh.shape["model"]
    assert kv["prefix_hit_tokens"] > 0
    print("SHARDED_PALLAS_OK")
""")


def _run(script: str, *args: str) -> str:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    r = subprocess.run([sys.executable, "-c", script, *args],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


@pytest.mark.slow
def test_sharded_engine_matches_single_device_and_generate():
    assert "SHARDED_MATRIX_OK" in _run(MATRIX_SCRIPT)


@pytest.mark.slow
def test_sharded_cancellation_and_spec_decode():
    assert "SHARDED_CANCEL_SPEC_OK" in _run(CANCEL_SPEC_SCRIPT)


@pytest.mark.slow
@pytest.mark.parametrize("spec", ["1,4", "2,2"],
                         ids=["model4", "data2-model2"])
def test_sharded_pallas_kernels_match_single_device(spec):
    assert "SHARDED_PALLAS_OK" in _run(PALLAS_SCRIPT, spec)
