"""Paged KV cache: differential serving harness + allocator properties.

The load-bearing guarantee is the differential harness: seeded random
traces (mixed prompt lengths, a shared system-prompt prefix, staggered
Poisson arrivals) are replayed through THREE independent decode paths —
one-shot ``generate``, the lock-step ``Engine``, and the paged
``ContinuousEngine`` — and the greedy tokens must be BIT-IDENTICAL across
all of them, with correct per-request completion metadata.  The paging
host layer (refcounted block allocator, hash-chained prefix cache, block
tables) is covered by property-based tests through the ``tests/_hyp``
shim: random alloc/free/fork sequences never leak or double-free blocks,
and a prefix-cache hit can never alias a block some live writer mutates.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, st

from repro.configs import get_config
from repro.models import build_model
from repro.serve import (BlockAllocator, ContinuousEngine, Engine,
                         PagedCacheManager, UnsupportedCacheError,
                         chain_keys, generate, make_trace, replay)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    return model, cfg


def _baseline(model, cfg, prompt, n, max_len=32):
    cache = model.init_cache(1, max_len, cfg, dtype=jnp.float32)
    out, _ = generate(model, jnp.asarray(prompt)[None, :], cache, n_steps=n)
    return np.asarray(out)[0]


def _prompts(lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


# ---- differential serving harness -------------------------------------------


def test_differential_trace_three_way(setup):
    """generate == lock-step Engine == paged ContinuousEngine, token for
    token, on a seeded trace with mixed lengths, a shared 6-token prefix,
    and staggered arrivals pushed through 3 recycled slots."""
    model, cfg = setup
    trace = make_trace(10, seed=13, load=0.7, min_prompt=2, max_prompt=10,
                       min_new=2, max_new=8, vocab=cfg.vocab,
                       shared_prefix=6)
    eng = ContinuousEngine(model, cfg, batch=3, max_len=32,
                           max_prompt_len=16, kv_layout="paged",
                           block_size=4)
    comps, _ = replay(eng, trace)
    assert len(comps) == len(trace)
    assert [c.uid for c in comps] == sorted(c.uid for c in comps)

    lock = Engine(model, cfg, batch=1, max_len=32, cache_dtype=jnp.float32)
    for (_, req), c in zip(trace, comps):  # trace order == uid order
        n = req.max_new_tokens
        ref_gen = _baseline(model, cfg, req.prompt, n)
        lock.reset()
        ref_lock = np.asarray(
            lock.greedy(jnp.asarray(req.prompt)[None, :], n))[0]
        np.testing.assert_array_equal(ref_gen, ref_lock)
        np.testing.assert_array_equal(
            np.array(c.tokens), ref_gen,
            err_msg=f"paged engine diverged for uid={c.uid} "
                    f"plen={req.prompt.size} n={n}")
        # completion metadata
        assert c.prompt_len == req.prompt.size
        assert c.finish_reason == "length"
        assert len(c.tokens) == n
        assert c.latency >= c.ttft >= 0
    # the shared 6-token prefix spans one full 4-token block; overlapping
    # requests hit it — and with LRU retention the hit survives pool drains
    # between staggered arrivals, so every request after the first hits
    assert eng.manager.prefix_hit_tokens >= 4
    # drained engine holds no live references; what survives is the warm
    # LRU of parked prefix blocks, each still indexed by the prefix cache
    assert eng.manager.fully_free
    assert len(eng.manager.prefix) == len(eng.manager.retained)
    assert (eng.manager.allocator.n_free
            + eng.manager.allocator.n_parked) == eng.n_blocks


def test_paged_matches_dense_layout(setup):
    """Same submissions through kv_layout='dense' and 'paged' produce
    identical tokens and finish metadata (block size chosen so it does not
    divide every prompt length)."""
    model, cfg = setup
    prompts = _prompts([5, 12, 8, 3, 10, 6], cfg.vocab, seed=21)
    budgets = [6, 4, 8, 5, 3, 7]
    outs = {}
    for layout in ("dense", "paged"):
        eng = ContinuousEngine(model, cfg, batch=2, max_len=32,
                               max_prompt_len=12, kv_layout=layout,
                               block_size=8)
        for p, n in zip(prompts, budgets):
            eng.submit(p, max_new_tokens=n)
        outs[layout] = eng.run()
        assert eng.kv_stats()["kv_layout"] == layout
    for cd, cp in zip(outs["dense"], outs["paged"]):  # both uid-sorted ==
        assert cd.prompt_len == cp.prompt_len         # submission order
        assert cd.tokens == cp.tokens
        assert cd.finish_reason == cp.finish_reason


def test_prefix_blocks_shared_and_refcounted(setup):
    """Two live requests with the same 8-token prompt share the two full
    prompt blocks (refcount 2) and still match the baseline exactly."""
    model, cfg = setup
    prompt = _prompts([8], cfg.vocab, seed=5)[0]
    eng = ContinuousEngine(model, cfg, batch=2, max_len=32,
                           max_prompt_len=12, kv_layout="paged",
                           block_size=4)
    eng.submit(prompt, max_new_tokens=6)
    eng.submit(prompt, max_new_tokens=6)
    eng.step()  # both admitted, one decode step: both still live
    assert eng.manager.prefix_hit_tokens == 8
    shared = [bid for bid in range(eng.n_blocks)
              if eng.manager.allocator.refcount[bid] == 2]
    assert len(shared) == 2  # the two full prompt blocks, nothing else
    ref = _baseline(model, cfg, prompt, 6)
    for c in eng.run():
        np.testing.assert_array_equal(np.array(c.tokens), ref)
    assert eng.manager.fully_free


def test_stop_token_metadata_on_paged_engine(setup):
    """Stop-token eviction (finish_reason + stop id included) survives the
    paged layout."""
    model, cfg = setup
    prompt = _prompts([6], cfg.vocab, seed=3)[0]
    ref = _baseline(model, cfg, prompt, 8)
    stop = int(ref[1]) if ref[1] != ref[0] else int(ref[0])
    first_hit = int(np.argmax(ref == stop))
    eng = ContinuousEngine(model, cfg, batch=2, max_len=32,
                           max_prompt_len=12, kv_layout="paged",
                           block_size=4)
    eng.submit(prompt, max_new_tokens=8, stop_ids=(stop,))
    (comp,) = eng.run()
    assert comp.finish_reason == "stop"
    assert comp.tokens == ref[:first_hit + 1].tolist()
    assert eng.manager.fully_free


def test_cache_full_frozen_slot_does_not_corrupt_neighbors(setup):
    """Regression: a slot evicted with finish_reason='cache_full' freezes at
    length == max_len; its per-step paged decode used to look up one entry
    past its block table, and take_along_axis's out-of-bounds fill
    (INT32_MIN) times block_size wraps around int32 to pool row 0 — so the
    'dropped' scatter landed stale K/V inside a LIVE request's first block,
    silently corrupting its tokens."""
    model, cfg = setup
    rng = np.random.default_rng(7)
    long_lived = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    cache_filler = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    outs = {}
    for layout in ("dense", "paged"):
        eng = ContinuousEngine(model, cfg, batch=2, max_len=16,
                               max_prompt_len=8, kv_layout=layout,
                               block_size=4)
        eng.submit(long_lived, max_new_tokens=12)    # owns pool block 0
        eng.submit(cache_filler, max_new_tokens=16)  # frozen at pos 16
        outs[layout] = {c.prompt_len: c for c in eng.run()}
    assert outs["paged"][6].finish_reason == "cache_full"
    for plen in (4, 6):
        assert outs["paged"][plen].tokens == outs["dense"][plen].tokens, \
            f"frozen cache-full slot corrupted prompt_len={plen}"


def test_table_upload_is_a_snapshot_of_the_mirror(setup):
    """The device block table is what the host mirror held at the flush.
    A host array handed to a transfer may be read after the call returns
    (and on the CPU an aligned one is aliased outright), while the
    manager edits its mirror in place at the next admission or release:
    uploading the mirror itself let those edits reach a table a program
    was already given."""
    model, cfg = setup
    eng = ContinuousEngine(model, cfg, batch=4, max_len=64,
                           max_prompt_len=16, kv_layout="paged",
                           block_size=4)
    mirror = eng.manager.tables
    raw = np.empty(mirror.nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64  # an aligned mirror, as the CPU aliases
    aligned = raw[start:start + mirror.nbytes].view(np.int32).reshape(
        mirror.shape)
    aligned[...] = mirror
    eng.manager.tables = aligned
    eng._table_dirty = True
    eng._flush_table()
    flushed = np.asarray(eng.cache.table).copy()
    aligned[...] = 0  # the manager's in-place edits after the flush
    np.testing.assert_array_equal(np.asarray(eng.cache.table), flushed)
    assert (flushed == eng.manager.sentinel).all()


# ---- cache-kind capability probe (serve / structured rejection) -------------


def test_hymba_serves_continuously():
    """Regression FLIP: sliding-window (hymba) configs used to be rejected
    with UnsupportedCacheError at construction — they now serve through
    per-slot ring + ssm state, degrading the default paged layout
    gracefully (prefix reuse off, no block reservation), with tokens
    matching the one-shot baseline."""
    cfg = get_config("hymba-1.5b").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(model, cfg, batch=2, max_len=32, max_prompt_len=8)
    stats = eng.kv_stats()
    assert stats["cache_kind"] == "hybrid"
    assert eng.manager is None  # block reservation / prefix cache inactive
    assert stats["kv_lane_tokens"] == cfg.window  # ring lanes, not max_len
    prompt = _prompts([6], cfg.vocab, seed=1)[0]
    eng.submit(prompt, max_new_tokens=5)
    (comp,) = eng.run()
    np.testing.assert_array_equal(np.array(comp.tokens),
                                  _baseline(model, cfg, prompt, 5))


def test_ssm_serves_continuously_in_both_requested_layouts():
    """Mamba used to raise in both layouts; the engine now serves it via
    per-slot conv/ssm state whichever layout the caller asked for (paged
    knobs degrade gracefully)."""
    cfg = get_config("mamba2-2.7b").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    prompt = _prompts([7], cfg.vocab, seed=2)[0]
    ref = _baseline(model, cfg, prompt, 4)
    for layout in ("paged", "dense"):
        eng = ContinuousEngine(model, cfg, batch=2, max_len=32,
                               max_prompt_len=8, kv_layout=layout)
        assert eng.kv_stats()["cache_kind"] == "ssm"
        eng.submit(prompt, max_new_tokens=4)
        (comp,) = eng.run()
        np.testing.assert_array_equal(np.array(comp.tokens), ref)


def test_whisper_rejected_with_unsupported_cache_error():
    """The mirror-image regression: enc-dec (whisper) still has no
    per-slot state and must be rejected with the structured error naming
    the remaining ROADMAP item (roadmap_item coverage survives the hymba
    flip)."""
    cfg = get_config("whisper-medium").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(UnsupportedCacheError) as ei:
        ContinuousEngine(model, cfg, batch=2, max_len=32, max_prompt_len=8)
    assert "Whisper" in ei.value.roadmap_item
    assert "enc-dec" in ei.value.roadmap_item
    assert isinstance(ei.value, ValueError)  # backwards compatible


# ---- allocator / prefix-cache unit tests ------------------------------------


def test_allocator_errors():
    a = BlockAllocator(4, 2)
    bid = a.alloc()
    a.free(bid)
    with pytest.raises(RuntimeError):
        a.free(bid)  # double free
    with pytest.raises(RuntimeError):
        a.fork(bid)  # fork of a free block
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [0, 1, 2, 3]
    with pytest.raises(RuntimeError):
        a.alloc()  # exhausted


def test_chain_keys_commit_to_full_prefix():
    bs = 4
    a = np.arange(10, dtype=np.int32)
    b = np.arange(10, dtype=np.int32)
    c = a.copy()
    c[1] = 99  # differ inside the FIRST block
    d = a.copy()
    d[5] = 99  # differ inside the SECOND block
    ka, kb, kc, kd = (chain_keys(t, bs) for t in (a, b, c, d))
    assert len(ka) == 2  # only full blocks get keys
    assert ka == kb
    assert ka[0] != kc[0] and ka[1] != kc[1]  # first-block change cascades
    assert ka[0] == kd[0] and ka[1] != kd[1]  # second-block change is local
    assert chain_keys(np.arange(3, dtype=np.int32), bs) == []


# ---- property-based: allocator + manager invariants -------------------------


@given(st.integers(0, 2**32 - 1))
def test_allocator_random_ops_never_leak_or_double_free(seed):
    """Random alloc/fork/free interleavings: refcounts always match an
    independent model, in-use + free always covers the pool, and releasing
    every reference returns the pool to fully free."""
    rng = np.random.default_rng(seed)
    alloc = BlockAllocator(n_blocks=8, block_size=4)
    live = {}  # bid -> expected refcount
    for _ in range(150):
        op = int(rng.integers(0, 3))
        if op == 0 and alloc.n_free:
            bid = alloc.alloc()
            assert bid not in live
            live[bid] = 1
        elif op == 1 and live:
            bid = int(rng.choice(sorted(live)))
            alloc.fork(bid)
            live[bid] += 1
        elif op == 2 and live:
            bid = int(rng.choice(sorted(live)))
            rc = alloc.free(bid)
            live[bid] -= 1
            assert rc == live[bid]
            if not live[bid]:
                del live[bid]
        assert alloc.n_in_use == len(live)
        assert alloc.n_free == alloc.n_blocks - len(live)
        for bid, rc in live.items():
            assert alloc.refcount[bid] == rc
    for bid, rc in list(live.items()):
        for _ in range(rc):
            alloc.free(bid)
    assert alloc.n_free == alloc.n_blocks
    assert (alloc.refcount == 0).all()


@given(st.integers(0, 2**32 - 1))
def test_manager_prefix_hits_never_alias_writable_blocks(seed):
    """Random admit/publish/release sequences with colliding prompt stems
    (LRU retention on for half the seeds): the blocks a new admission may
    WRITE (its scatter destinations) are always exclusively owned
    (refcount 1, no other slot maps them), shared prefix blocks are only
    ever read, parked blocks never hold a reference, and draining every
    slot leaves no live references — just the warm LRU, fully indexed by
    the prefix cache."""
    rng = np.random.default_rng(seed)
    bs, batch, max_len = 4, 4, 32
    retain = int(rng.integers(0, 9)) if seed % 2 else 0
    mgr = PagedCacheManager(n_blocks=24, block_size=bs, batch=batch,
                            max_len=max_len, retain_blocks=retain)
    stems = [rng.integers(0, 256, 8).astype(np.int32) for _ in range(2)]
    owned = {}  # slot -> set of mapped block ids
    for _ in range(60):
        free_slots = [s for s in range(batch) if s not in owned]
        do_admit = free_slots and (not owned or rng.random() < 0.6)
        if do_admit:
            slot = int(rng.choice(free_slots))
            stem = stems[int(rng.integers(0, len(stems)))]
            suffix = rng.integers(0, 256, int(rng.integers(0, 5))
                                  ).astype(np.int32)
            prompt = np.concatenate([stem, suffix])
            total = min(len(prompt) + int(rng.integers(1, 6)), max_len)
            if not mgr.can_admit(prompt, total):
                continue
            cached, hits = mgr.admit(slot, prompt, total)
            assert cached % bs == 0 and cached <= len(prompt)
            assert len(hits) * bs == cached
            dst = mgr.scatter_rows(slot, 0, len(prompt), lo=cached,
                                   hi=len(prompt))
            mapped = dst[dst < mgr.sentinel * bs]
            writable = {int(b) for b in mapped // bs}
            assert not writable & set(hits)  # hit blocks are read-only
            for other, blocks in owned.items():
                assert not writable & blocks, \
                    f"slot {slot} would write blocks mapped by slot {other}"
            for bid in writable:
                assert mgr.allocator.refcount[bid] == 1
            # the writer sometimes finishes its prefill (publishing its
            # registered full blocks), sometimes releases mid-pending
            if rng.random() < 0.7:
                mgr.publish(slot, len(prompt))
            owned[slot] = {int(b) for b in mgr.tables[slot]
                           if b != mgr.sentinel}
        elif owned:
            slot = int(rng.choice(sorted(owned)))
            mgr.release(slot)
            del owned[slot]
        in_use = {b for blocks in owned.values() for b in blocks}
        assert mgr.allocator.n_in_use == len(in_use)
        assert len(mgr.retained) <= retain
        for bid in mgr.retained:
            assert mgr.allocator.refcount[bid] == 0
            assert bid not in in_use
    for slot in sorted(owned):
        mgr.release(slot)
    assert mgr.fully_free
    assert len(mgr.prefix) == len(mgr.retained)
    assert mgr.allocator.n_free + mgr.allocator.n_parked == 24
