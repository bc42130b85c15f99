"""Paged KV-cache subsystem: allocator invariants, paged-vs-dense bitwise
parity, copy-on-write prefix sharing, and offload/wake round-trips.

The load-bearing properties:

  * paged decode is BITWISE identical to the dense per-slot cache — the
    block pool is a memory-layout change, never a numerics change;
  * a shared prefix is shared by reference only: a request diverging from
    it (or being preempted off it) must never perturb a co-resident;
  * every terminal status releases its blocks — the pool drains to empty
    after any serve, whatever mix of ok/timeout/failed the workload hit.

Allocator invariants are checked twice: structurally (``BlockPool.audit``)
and by replaying the ``page_*`` event stream a serve left behind — the
event log alone must prove no block was double-freed or handed out while
still referenced.
"""
import numpy as np
import pytest

from repro.engine import (BlockPool, PoolExhausted, Request, RunSpec,
                          ServePolicy)
from repro.engine.serve import ServeEngine

SPEC = RunSpec(arch="stablelm-1.6b", reduced=True, mesh_data=1, mesh_model=1)


def _prompt(rng, n, vocab=500):
    return rng.integers(0, vocab, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# BlockPool (host-side, no jax)
# ---------------------------------------------------------------------------

def test_blockpool_refcounts_drain_and_blocks_return():
    pool = BlockPool(8, 4, prefix_cache=False)
    rng = np.random.default_rng(0)
    hist, cow = pool.admit(0, _prompt(rng, 10))    # 3 blocks
    assert hist == 0 and cow is None               # no prefix cache
    pool.admit(1, _prompt(rng, 7))                 # 2 blocks
    assert pool.blocks_in_use() == 5
    pool.audit()
    pool.release_slot(0)
    pool.release_slot(1)
    pool.release_slot(1)                           # idempotent
    assert pool.blocks_in_use() == 0
    assert (pool.ref == 0).all()
    assert sorted(pool.free) == list(range(8))     # all blocks came back
    pool.audit()


def test_blockpool_prefix_sharing_and_full_match_cow():
    pool = BlockPool(16, 4)
    p = np.arange(12, dtype=np.int32)
    h0, c0 = pool.admit(0, p)
    assert h0 == 0 and c0 is None                  # cold: nothing cached
    # identical prompt: full match -> hist = plen-1, last block CoW'd
    h1, c1 = pool.admit(1, p)
    assert h1 == 11 and c1 is not None
    src, dst, logical = c1
    assert logical == 2 and pool.slot_blocks[1][2] == dst
    # the two leading blocks are aliased by reference, not copied
    assert pool.slot_blocks[0][:2] == pool.slot_blocks[1][:2]
    assert all(pool.ref[b] == 2 for b in pool.slot_blocks[0][:2])
    # a PARTIAL match shares only the matched whole blocks, no CoW
    q = np.concatenate([p[:8], np.array([90, 91, 92, 93], np.int32)])
    h2, c2 = pool.admit(2, q)
    assert h2 == 8 and c2 is None
    assert pool.slot_blocks[2][:2] == pool.slot_blocks[0][:2]
    pool.audit()
    for s in (0, 1, 2):
        pool.release_slot(s)
    assert (pool.ref == 0).all()
    # registered prefix blocks stay cached (reclaimable), not free
    assert set(pool.lru) == set(pool.registered)
    pool.audit()


def test_blockpool_exhaustion_rolls_back_and_reclaims_lru():
    pool = BlockPool(3, 4)
    rng = np.random.default_rng(1)
    pool.admit(0, _prompt(rng, 12))                # all 3 blocks
    with pytest.raises(PoolExhausted):
        pool.admit(1, _prompt(rng, 4))
    assert 1 not in pool.slot_blocks               # rolled back cleanly
    assert pool.blocks_in_use() == 3
    pool.audit()
    pool.release_slot(0)                           # blocks -> prefix LRU
    assert pool.blocks_in_use() == 0 and not pool.free
    pool.admit(1, _prompt(rng, 12))                # reclaims all 3 via LRU
    assert pool.blocks_in_use() == 3
    pool.audit()


def test_blockpool_exhaustion_rollback_deregisters_unwritten():
    """A rolled-back admission must not leave its never-prefilled blocks
    registered: a retry (the engine's normal exhaustion path) would get
    prefix hits on blocks whose content was never written and decode over
    zero/garbage KV."""
    pool = BlockPool(3, 4)
    p = np.arange(16, dtype=np.int32)              # needs 4 blocks > pool
    with pytest.raises(PoolExhausted):
        pool.admit(0, p)
    assert not pool.registered and not pool.by_hash and not pool.lru
    h, cow = pool.admit(1, p[:12])                 # same leading blocks fit
    assert h == 0 and cow is None, \
        "phantom prefix hit on blocks that were never prefilled"
    pool.audit()


def test_blockpool_pending_tail_not_matchable_until_written():
    """A block registered by a shared-tail admission holds no content until
    the engine's round executes its prefill: matching it — or using it as a
    CoW source — before mark_written() would read unwritten KV."""
    pool = BlockPool(16, 4)
    base = np.arange(8, dtype=np.int32)
    p = np.concatenate([base, np.array([50, 51, 52, 53], np.int32)])
    pool.admit(0, base)                    # fresh plan: matchable at once
    pool.mark_written()
    h1, c1 = pool.admit(1, p)              # partial hit -> tail is PENDING
    assert h1 == 8 and c1 is None
    h2, c2 = pool.admit(2, p)              # same round, identical prompt
    assert h2 == 8 and c2 is None, \
        "matched a tail block whose prefill has not run yet"
    pool.mark_written()
    h3, c3 = pool.admit(3, p)              # next round: fully matchable
    assert h3 == 11 and c3 is not None
    pool.audit()


def test_blockpool_audit_catches_aliased_writable_block():
    pool = BlockPool(4, 4, prefix_cache=False)
    rng = np.random.default_rng(2)
    pool.admit(0, _prompt(rng, 4))
    b = pool.slot_blocks[0][0]
    # alias the block into a second slot WITHOUT registering it
    pool.ref[b] += 1
    pool.slot_blocks[1] = [b]
    with pytest.raises(AssertionError, match="aliased"):
        pool.audit()


# ---------------------------------------------------------------------------
# Engine level
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_engine():
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, verbose=False)
    eng.build()
    return eng


@pytest.fixture(scope="module")
def paged_engine():
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, verbose=False)
    eng.build()
    return eng


@pytest.fixture(scope="module")
def paged_nopfx():
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, prefix_cache=False, verbose=False)
    eng.build()
    return eng


def _staggered(n=5, seed=3):
    rng = np.random.default_rng(seed)
    arrivals = [0, 1, 3, 5, 8, 11, 13][:n]
    gens = [8, 3, 6, 2, 8, 4, 7][:n]
    return [Request(rid=i, prompt=_prompt(rng, int(rng.integers(5, 17))),
                    max_gen=gens[i], arrival_step=arrivals[i])
            for i in range(n)]


def _tokens(res):
    return {r.rid: r.tokens.tolist() for r in res["requests"]}


def test_paged_matches_dense_staggered(paged_nopfx, dense_engine):
    """Paged decode through the block table is bitwise identical to the
    dense per-slot cache under staggered admission — same prompts, same
    arrival steps, same slots, greedy decode."""
    res_p = paged_nopfx.serve(_staggered(), max_slots=2)
    res_d = dense_engine.serve(_staggered(), max_slots=2)
    assert res_p["metrics"]["admitted_mid_decode"] > 0
    assert res_p["metrics"]["status_counts"] == {"ok": 5}
    assert _tokens(res_p) == _tokens(res_d)


def test_poisoned_pool_never_leaks_unwritten_lanes(monkeypatch,
                                                   dense_engine):
    """Leak canary: PAGED_POISON=1 fills the pool (trash block included)
    with NaN at init, so any read of a never-written lane that escapes the
    masks becomes NaN logits -> token 0 instead of a silent zero-read.
    Parity with dense under poison proves every kept token was computed
    from lanes the engine actually wrote (this caught a real race: the
    async host->device table upload reading an in-place-mutated table)."""
    monkeypatch.setenv("PAGED_POISON", "1")
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, prefix_cache=False, verbose=False)
    res_p = eng.serve(_staggered(), max_slots=2)
    res_d = dense_engine.serve(_staggered(), max_slots=2)
    assert res_p["metrics"]["status_counts"] == {"ok": 5}
    assert _tokens(res_p) == _tokens(res_d)


def test_prefix_sharing_warm_hit_rate_and_parity():
    """Re-serving the same prompts hits the prefix cache for all but the
    last token of each prompt (> 0.9 of prefill work skipped) and the
    tokens are bitwise identical to the cold serve. The pool must be large
    enough to RETAIN the registered prefixes — a pool sized below the
    working set thrashes the LRU and the hit rate collapses to 0."""
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, kv_pool_blocks=40, verbose=False)
    rng = np.random.default_rng(9)
    prompts = [_prompt(rng, 16) for _ in range(4)]

    def reqs(base):
        return [Request(rid=base + i, prompt=p, max_gen=8)
                for i, p in enumerate(prompts)]

    cold = eng.serve(reqs(0), max_slots=4)
    warm = eng.serve(reqs(100), max_slots=4)
    pg = warm["metrics"]["paging"]
    assert pg["prefix_hit_rate"] > 0.9, pg
    assert pg["marginal_prefill_tokens"] < pg["prefill_tokens_requested"]
    cold_t = {r.rid % 100: r.tokens.tolist() for r in cold["requests"]}
    warm_t = {r.rid % 100: r.tokens.tolist() for r in warm["requests"]}
    assert warm_t == cold_t


def test_cow_divergence_never_perturbs_co_residents(paged_engine,
                                                    paged_nopfx):
    """Requests sharing a 12-token prefix then diverging: every stream must
    equal its unshared solo serve — writes into a shared block go through
    copy-on-write, never in place."""
    rng = np.random.default_rng(21)
    prefix = _prompt(rng, 12)
    reqs = [Request(rid=i,
                    prompt=np.concatenate([prefix, _prompt(rng, 4)]),
                    max_gen=8)
            for i in range(3)]
    shared = paged_engine.serve(
        [Request(rid=r.rid, prompt=r.prompt, max_gen=8) for r in reqs],
        max_slots=3)
    assert shared["metrics"]["paging"]["prefix_hit_rate"] > 0
    for r in reqs:
        solo = paged_nopfx.serve(
            [Request(rid=r.rid, prompt=r.prompt, max_gen=8)], max_slots=3)
        assert _tokens(shared)[r.rid] == _tokens(solo)[r.rid], \
            f"request {r.rid} perturbed by its shared prefix"


def test_identical_prompts_in_one_batch_share_and_match(paged_engine):
    """Identical prompts admitted TOGETHER share within the batch (blocks
    are registered at allocation time); full-match CoW keeps each row's
    final block private and the streams identical."""
    rng = np.random.default_rng(5)
    p = _prompt(rng, 16)
    res = paged_engine.serve(
        [Request(rid=i, prompt=p, max_gen=6) for i in range(3)],
        max_slots=3)
    assert res["metrics"]["paging"]["cow_copies"] >= 2
    toks = _tokens(res)
    assert toks[0] == toks[1] == toks[2]


def test_same_round_shared_tail_cow_parity():
    """Request A extends a cached prefix with a prompt ending on a block
    boundary (its tail block is registered at allocation time); request B
    carries the identical prompt in the SAME admission round. B must not
    CoW-copy or read A's tail block before A's shared-tail prefill writes
    it — both streams must match an unshared solo serve bitwise."""
    eng = ServeEngine(SPEC, prompt_len=24, gen=6, paged=True,
                      kv_block_size=4, kv_pool_blocks=48, verbose=False)
    rng = np.random.default_rng(31)
    base = _prompt(rng, 16)
    ext = np.concatenate([base, _prompt(rng, 8)])   # 24 tokens, % 4 == 0
    eng.serve([Request(rid=0, prompt=base, max_gen=4)], max_slots=2)
    res = eng.serve([Request(rid=1, prompt=ext.copy(), max_gen=6),
                     Request(rid=2, prompt=ext.copy(), max_gen=6)],
                    max_slots=2)
    assert res["metrics"]["paging"]["prefix_hit_rate"] > 0
    solo_eng = ServeEngine(SPEC, prompt_len=24, gen=6, paged=True,
                           kv_block_size=4, prefix_cache=False,
                           verbose=False)
    solo = solo_eng.serve([Request(rid=9, prompt=ext.copy(), max_gen=6)],
                          max_slots=1)
    toks = _tokens(res)
    assert toks[1] == toks[2] == _tokens(solo)[9], \
        "same-round shared-tail admission read/copied unwritten blocks"
    pool = eng._paged_state["pool"]
    assert pool.blocks_in_use() == 0 and not pool.pending
    pool.audit()


def test_poison_quarantine_spares_shared_prefix_and_registry():
    """A poison_request fault on one request of a shared-prefix trio must
    quarantine ONLY that request: co-residents sharing its prefix blocks
    finish bitwise intact, and the prefix registry never serves a NaN block
    to a later request."""
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, resilience="poison_request@1",
                      verbose=False)
    rng = np.random.default_rng(41)
    p = _prompt(rng, 16)
    res = eng.serve([Request(rid=i, prompt=p.copy(), max_gen=8)
                     for i in range(3)], max_slots=3)
    statuses = {r.rid: r.status for r in res["requests"]}
    assert statuses == {0: "ok", 1: "failed", 2: "ok"}, statuses
    clean = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                        kv_block_size=4, prefix_cache=False, verbose=False)
    ref = clean.serve([Request(rid=0, prompt=p.copy(), max_gen=8)],
                      max_slots=3)
    assert _tokens(res)[0] == _tokens(res)[2] == _tokens(ref)[0], \
        "poisoned row leaked NaN into co-residents sharing its prefix"
    # warm re-serve: the registry must hit the (un-poisoned) prefix blocks
    res2 = eng.serve([Request(rid=10, prompt=p.copy(), max_gen=8)],
                     max_slots=3)
    assert {r.status for r in res2["requests"]} == {"ok"}
    assert res2["metrics"]["paging"]["prefix_hit_rate"] > 0.9
    assert _tokens(res2)[10] == _tokens(ref)[0], \
        "prefix registry served a block poisoned by the quarantined row"
    pool = eng._paged_state["pool"]
    assert pool.blocks_in_use() == 0
    pool.audit()


@pytest.mark.parametrize("level", [1, 2])
def test_pool_exhaustion_preemption_roundtrip(level):
    """A pool too small for the workload forces preemption; sleep level 1
    (host offload, bitwise restore) and level 2 (discard + re-prefill) must
    both finish every request with tokens identical to an unpressured
    pool."""
    def reqs():
        rng = np.random.default_rng(7)
        return [Request(rid=i, prompt=_prompt(rng, 16), max_gen=12)
                for i in range(4)]

    tiny = ServeEngine(SPEC, prompt_len=16, gen=12, paged=True,
                       kv_block_size=4, kv_pool_blocks=16,
                       prefix_cache=False, sleep_level=level, verbose=False)
    res = tiny.serve(reqs(), max_slots=4, max_steps=500)
    pg = res["metrics"]["paging"]
    assert res["metrics"]["status_counts"] == {"ok": 4}
    assert pg["preemptions"] > 0, "workload too tame: no pool pressure"
    if level == 1:
        assert pg["offloads"] > 0 and pg["wakes"] > 0
    else:
        assert pg["offloads"] == 0 and pg["wakes"] > 0

    big = ServeEngine(SPEC, prompt_len=16, gen=12, paged=True,
                      kv_block_size=4, prefix_cache=False, verbose=False)
    ref = big.serve(reqs(), max_slots=4)
    assert ref["metrics"]["paging"]["preemptions"] == 0
    assert _tokens(res) == _tokens(ref), \
        f"sleep level {level} round-trip diverged"

    # allocator invariant replay from the event stream alone: a block is
    # only handed out while unreferenced, never double-freed, and every
    # reference is eventually dropped
    ref_replay = {}
    for ev in res["events"]:
        kind = ev[0]
        if not kind.startswith("page_"):
            continue
        _, _, slot, block = ev
        if kind == "page_alloc":
            assert ref_replay.get(block, 0) == 0, \
                f"block {block} allocated while still referenced"
            ref_replay[block] = 1
        elif kind == "page_share":
            ref_replay[block] = ref_replay.get(block, 0) + 1
        elif kind == "page_cow":
            src, dst = block
            assert ref_replay.get(dst, 0) == 0
            ref_replay[dst] = 1
            ref_replay[src] -= 1
            assert ref_replay[src] >= 0, f"block {src} double-freed (cow)"
        elif kind == "page_free":
            ref_replay[block] = ref_replay.get(block, 0) - 1
            assert ref_replay[block] >= 0, f"block {block} double-freed"
    assert all(v == 0 for v in ref_replay.values()), \
        f"leaked references at end of serve: {ref_replay}"

    # structural audit of the live pool agrees: fully drained
    pool = tiny._paged_state["pool"]
    assert pool.blocks_in_use() == 0
    pool.audit()


def test_terminal_statuses_release_blocks():
    """Satellite 1: every terminal path — completion, deadline timeout,
    poison quarantine — returns its blocks; the pool is empty after serve
    whatever the status mix."""
    eng = ServeEngine(SPEC, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, resilience="poison_request@1",
                      verbose=False)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=_prompt(rng, 16), max_gen=8,
                    deadline_steps=3 if i == 2 else None)
            for i in range(4)]
    res = eng.serve(reqs, max_slots=4, max_steps=200)
    statuses = {r.rid: r.status for r in res["requests"]}
    assert statuses[1] == "failed" and statuses[2] == "timeout"
    assert statuses[0] == "ok" and statuses[3] == "ok"
    pool = eng._paged_state["pool"]
    assert pool.blocks_in_use() == 0, \
        f"terminal statuses leaked blocks: {statuses}"
    pool.audit()
    # survivors were not perturbed by the quarantined row's NaN blocks
    for r in res["requests"]:
        if r.status == "ok":
            assert np.isfinite(r.tokens).all() and len(r.tokens) == 8


def test_peak_occupancy_independent_of_max_len():
    """The acceptance property: with a fixed pool, peak block occupancy
    tracks the tokens actually resident, NOT the engine's max cache length
    — growing ``gen`` (hence cache_len) must not move the peak."""
    def reqs():
        rng = np.random.default_rng(13)
        return [Request(rid=i, prompt=_prompt(rng, 16), max_gen=6)
                for i in range(4)]

    peaks = []
    for gen in (8, 32):
        eng = ServeEngine(SPEC, prompt_len=16, gen=gen, paged=True,
                          kv_block_size=4, kv_pool_blocks=32,
                          prefix_cache=False, verbose=False)
        res = eng.serve(reqs(), max_slots=2)
        peaks.append(res["metrics"]["paging"]["blocks_in_use_peak"])
    assert peaks[0] == peaks[1], \
        f"peak occupancy scaled with max_len: {peaks}"


def test_pallas_paged_backend_matches_jnp(paged_engine):
    """The Pallas block-table kernels (paged_attn=pallas) produce the same
    tokens as the jnp gather reference."""
    spec = RunSpec(arch="stablelm-1.6b", reduced=True, mesh_data=1,
                   mesh_model=1, kernels="paged_attn=pallas")
    eng = ServeEngine(spec, prompt_len=16, gen=8, paged=True,
                      kv_block_size=4, verbose=False)
    res_p = eng.serve(_staggered(seed=17), max_slots=2)
    res_j = paged_engine.serve(_staggered(seed=17), max_slots=2)
    assert _tokens(res_p) == _tokens(res_j)


def test_batch_axes_discovered_once_per_engine(dense_engine, monkeypatch):
    """Satellite 2: ``cache_batch_axes`` eval_shape discovery runs once per
    engine build and is reused from ``_cache_axes`` afterwards."""
    from repro.engine import batching

    calls = {"n": 0}
    real = batching.cache_batch_axes

    def counting(init_fn):
        calls["n"] += 1
        return real(init_fn)

    monkeypatch.setattr(batching, "cache_batch_axes", counting)
    dense_engine._cache_axes = None                # force re-discovery
    from repro.models import init_cache
    init = lambda b: init_cache(dense_engine.cfg, b, 24)
    a1 = dense_engine._batch_axes(init)
    a2 = dense_engine._batch_axes(init)
    assert calls["n"] == 1 and a1 is a2


def test_sleep2_wake_on_shared_prefix_under_preemption_storm():
    """Satellite: a preemption storm at sleep level 2 (discard +
    re-prefill) hitting rows whose PREFIX BLOCKS ARE SHARED. A preempted
    row drops its references and its wake re-admits through the prefix
    registry — the round-trip must neither corrupt the shared blocks nor
    leak a reference: refcounts drain to zero and every co-resident's
    stream is bitwise identical to an unpressured pool's."""
    rng = np.random.default_rng(23)
    prefix = _prompt(rng, 12)
    suffixes = [_prompt(rng, 4) for _ in range(4)]

    def reqs():
        return [Request(rid=i,
                        prompt=np.concatenate([prefix, suffixes[i]]),
                        max_gen=12)
                for i in range(4)]

    tiny = ServeEngine(SPEC, prompt_len=16, gen=12, paged=True,
                       kv_block_size=4, kv_pool_blocks=14,
                       sleep_level=2, verbose=False)
    res = tiny.serve(reqs(), max_slots=4, max_steps=800)
    pg = res["metrics"]["paging"]
    assert res["metrics"]["status_counts"] == {"ok": 4}
    assert pg["preemptions"] > 0, "workload too tame: no pool pressure"
    assert pg["offloads"] == 0 and pg["wakes"] > 0    # level 2: discard only
    assert pg["prefix_hit_rate"] > 0, "prefixes were never shared"

    big = ServeEngine(SPEC, prompt_len=16, gen=12, paged=True,
                      kv_block_size=4, verbose=False)
    ref = big.serve(reqs(), max_slots=4)
    assert ref["metrics"]["paging"]["preemptions"] == 0
    assert _tokens(res) == _tokens(ref), \
        "level-2 wake on shared prefixes diverged"

    pool = tiny._paged_state["pool"]
    assert pool.blocks_in_use() == 0 and (pool.ref == 0).all()
    assert not pool.pending
    pool.audit()


def test_decode_block_counters_match_hand_count(paged_nopfx):
    """``decode_blocks_live`` sums, over decode steps, the 4-token blocks
    under each row's length as the kernel sees it (the new token
    included; a free row holds none); ``decode_blocks_visited`` sums the
    whole table, slots x blocks a row."""
    rng = np.random.default_rng(5)
    reqs = [Request(rid=0, prompt=_prompt(rng, 5), max_gen=4,
                    arrival_step=0),
            Request(rid=1, prompt=_prompt(rng, 6), max_gen=3,
                    arrival_step=2)]
    res = paged_nopfx.serve(reqs, policy=ServePolicy(max_slots=2))
    m, pg = res["metrics"], res["metrics"]["paging"]
    assert m["status_counts"] == {"ok": 2}
    # the first token of each request comes from its prefill; each later
    # one from a decode step at length prompt + tokens so far:
    #   step 1: A at 6                2 blocks
    #   step 2: A at 7                2
    #   step 3: A at 8, B (admitted) at 7   2 + 2
    #   step 4: B at 8 (A finished)   2
    assert m["decode_steps"] == 4
    assert pg["decode_blocks_live"] == 2 + 2 + (2 + 2) + 2
    nb = paged_nopfx._paged_state["nb_max"]
    assert pg["decode_blocks_visited"] == 4 * 2 * nb
