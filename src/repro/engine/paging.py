"""Paged KV-cache subsystem: block pool, prefix sharing, host-RAM offload.

The serving engine's dense decode cache reserves ``[max_slots, max_len]`` KV
rows per layer — one long-context request inflates every slot's reservation,
and identical system prompts are prefilled and stored once per request. This
module replaces that reservation with a vLLM-style paged cache:

* **BlockPool** — the host-side allocator. KV lives in ``num_blocks`` fixed
  ``block_size``-token blocks shared by all slots (one physical block id
  spans every layer); each slot owns an ordered list of blocks, mirrored
  into the device block table ``cache["table"] [B, nb_max]``. Freed blocks
  return to a free list; **prefix sharing** registers every full prompt
  block under a chained content hash, so a later request whose prompt starts
  with the same blocks just bumps their refcounts and skips prefilling them
  (``hist`` tokens served from cache). Shared blocks are immutable;
  **copy-on-write** (`ensure written blocks are exclusive`) allocates a
  private copy before any write would touch a block another slot (or the
  prefix registry) can still see.

* **Sleep levels** — vLLM-style memory release for idle/preempted requests:
  level 1 offloads a slot's blocks to host RAM (``gather_slot`` → numpy) and
  frees them; wake re-allocates and uploads (bitwise round-trip). Level 2
  discards the blocks entirely; wake re-prefills prompt + generated tokens.

* **Device helpers** — pure jax functions the engine jits once per shape:
  ``scatter_prefill`` (splice a dense ragged-prefilled cache into the pool —
  the bitwise-exact admission path), ``gather_slot`` / ``upload_slot``
  (offload/wake), ``copy_blocks`` (CoW), and paged twins of the resilience
  layer's row-health/poison functions (pool leaves have no batch axis, so
  the dense ``cache_batch_axes`` machinery cannot see rows — these go
  through the table instead).

Every allocator transition is appended to the engine's event log
(``page_alloc | page_share | page_cow | page_free | page_offload |
page_wake``), so tests can replay allocator invariants (no double-free, no
aliased writable blocks) from ``engine.events`` alone.

Trash-block convention: pool arrays have ``num_blocks + 1`` physical slots;
the last one backs unallocated table entries on the READ side and is never
written — masked or invalid scatter writes are dropped with an
out-of-bounds index instead (duplicate scatter indices have no defined
winner, so funnelling many rows' dead writes into one shared block would
be racy). The trash block therefore stays all-zero, and read paths in
``models.attention`` / ``kernels.paged_attention`` additionally zero V
outside validity, so whatever a freed or quarantined row left in its own
blocks (even NaN) cannot leak into live rows.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

PyTree = Any

# layer-group keys a paged cache may carry (matching models.model)
PAGED_GROUPS = ("dense", "moe")


def round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


class PoolExhausted(RuntimeError):
    """No free block and nothing reclaimable: the engine must preempt."""


@dataclass
class Parked:
    """A preempted request's saved state (sleep level 1 keeps the payload)."""
    rid: Any
    level: int
    n_tokens: int                      # valid cache length at preemption
    generated: List[int]               # tokens emitted so far
    payload: Optional[dict] = None     # level 1: host copies of k/v blocks
    last_token: Optional[int] = None   # level 1: resume decode input
    key_row: Optional[np.ndarray] = None  # level 1: sampling key row


class BlockPool:
    """Host-side block allocator + prefix registry (no jax — pure Python).

    ``events`` is a list shared with the engine; every transition appends
    ``(kind, step, slot, block)`` tuples (``self.step`` is advanced by the
    engine loop). Refcounts count *slot* references; a registered block with
    refcount 0 stays cached (reclaimable LRU) until the free list runs dry.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 events: Optional[list] = None, prefix_cache: bool = True):
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.trash = num_blocks
        self.prefix_cache = prefix_cache
        self.events = events if events is not None else []
        self.step = 0
        self.free: List[int] = list(range(num_blocks - 1, -1, -1))
        self.ref = np.zeros(num_blocks, np.int64)
        self.slot_blocks: Dict[int, List[int]] = {}
        self.registered: Dict[int, int] = {}      # block -> chained hash
        self.by_hash: Dict[int, int] = {}         # chained hash -> block
        self.lru: Dict[int, int] = {}             # reclaimable cached blocks
        # registered blocks whose content-producing prefill has NOT run yet
        # (a shared-tail admission registers at allocation; the engine calls
        # mark_written() once the round's prefills execute). They must not
        # be prefix-matched or used as a CoW source until then.
        self.pending: set = set()
        self._tick = 0
        # stats
        self.in_use_peak = 0
        self.prefix_lookup_tokens = 0
        self.prefix_hit_tokens = 0
        self.cow_copies = 0

    # -- bookkeeping --------------------------------------------------------

    def _log(self, kind: str, slot, block):
        self.events.append((kind, self.step, slot, block))

    def blocks_in_use(self) -> int:
        return int(np.count_nonzero(self.ref))

    def _bump_peak(self):
        self.in_use_peak = max(self.in_use_peak, self.blocks_in_use())

    def reset_stats(self):
        self.in_use_peak = self.blocks_in_use()
        self.prefix_lookup_tokens = 0
        self.prefix_hit_tokens = 0
        self.cow_copies = 0

    # -- allocation core ----------------------------------------------------

    def _deregister(self, b: int):
        h = self.registered.pop(b, None)
        if h is not None and self.by_hash.get(h) == b:
            del self.by_hash[h]
        self.lru.pop(b, None)
        self.pending.discard(b)

    def _alloc_raw(self) -> int:
        if self.free:
            return self.free.pop()
        if self.lru:   # reclaim the least-recently-cached prefix block
            b = min(self.lru, key=self.lru.get)
            self._deregister(b)
            return b
        raise PoolExhausted(
            f"block pool exhausted ({self.num_blocks} blocks of "
            f"{self.block_size} tokens, {self.blocks_in_use()} in use)")

    def _take(self, slot: int, b: int):
        self.ref[b] += 1
        self.slot_blocks.setdefault(slot, []).append(b)

    def _drop(self, slot: int, b: int):
        assert self.ref[b] > 0, f"double free of block {b}"
        self.ref[b] -= 1
        self._log("page_free", slot, b)
        if self.ref[b] == 0:
            if b in self.registered:
                self._tick += 1
                self.lru[b] = self._tick
            else:
                self.free.append(b)

    # -- public API ---------------------------------------------------------

    def prefix_hashes(self, prompt) -> List[int]:
        """Chained hash per FULL block of the prompt (partial tail excluded)."""
        bs = self.block_size
        hashes, h = [], 0
        for j in range(len(prompt) // bs):
            h = hash((h, tuple(int(t) for t in prompt[j * bs:(j + 1) * bs])))
            hashes.append(h)
        return hashes

    def admit(self, slot: int, prompt, pending_all: bool = False
              ) -> Tuple[int, Optional[Tuple[int, int, int]]]:
        """Allocate the slot's block list for ``prompt``; returns ``(hist,
        cow)``. ``hist`` is the number of leading tokens already present in
        shared prefix blocks — a multiple of block_size, EXCEPT when the
        whole prompt is cached: then hist is capped at ``len(prompt) - 1``
        (every admission must compute at least one position for its first
        logits) and the block holding that last position is copy-on-write
        swapped for a private copy (``cow = (src, dst, logical)``; the
        caller must device-copy src -> dst before prefilling into it). Full
        blocks this request prefills are registered for future sharing at
        ALLOCATION time, so two identical prompts in one admission batch
        share within the batch — but a block registered by a SHARED-tail
        admission is ``pending`` (its prefill runs after the round's fresh
        prefills and after CoW copies) and is not matchable until the
        engine calls :meth:`mark_written`; matching stops at the first
        pending block so nothing reads or CoW-copies unwritten content.
        ``pending_all=True`` (chunked prefill) marks EVERY block this
        admission registered as pending regardless of a prefix hit — the
        content lands one chunk at a time over several engine iterations,
        so nothing may match these blocks until the final chunk's
        :meth:`mark_written`. Raises PoolExhausted with no state change
        (blocks this admission registered are deregistered again — their
        content was never written, so a retry must not see them as prefix
        hits)."""
        if slot in self.slot_blocks:
            raise RuntimeError(f"slot {slot} already holds blocks")
        plen = len(prompt)
        bs = self.block_size
        hashes = self.prefix_hashes(prompt) if self.prefix_cache else []
        matched: List[int] = []
        for h in hashes:
            b = self.by_hash.get(h)
            if b is None or b in self.pending:
                break
            matched.append(b)
        full = bool(matched) and len(matched) * bs >= plen
        hist = plen - 1 if full else len(matched) * bs
        self.prefix_lookup_tokens += plen
        self.prefix_hit_tokens += hist

        n_total = -(-plen // bs)
        cow = None
        newly_registered: List[int] = []
        try:
            for b in matched:
                self._take(slot, b)
                self.lru.pop(b, None)
                self._log("page_share", slot, b)
            for j in range(len(matched), n_total):
                b = self._alloc_raw()
                self._take(slot, b)
                self._log("page_alloc", slot, b)
                if self.prefix_cache and j < plen // bs:
                    h = hashes[j]
                    self.registered[b] = h
                    self.by_hash[h] = b
                    newly_registered.append(b)
            if full:
                # the tail re-computation will WRITE position plen - 1,
                # which lives inside a shared block — un-share it now
                _, cow = self.prepare_write(slot, plen - 1)
        except PoolExhausted:
            for b in newly_registered:
                self._deregister(b)
            self.release_slot(slot)   # roll back; the engine may preempt
            raise
        if hist > 0 or pending_all:
            # a prefix hit means the engine prefills only the TAIL (the
            # "shared" plan, which runs after fresh prefills and CoW) —
            # until that prefill executes these blocks hold no content.
            # Chunked admissions (pending_all) fill even hist-0 blocks
            # incrementally, so the same discipline applies to all of them.
            self.pending.update(newly_registered)
        self._bump_peak()
        return hist, cow

    def release_slot(self, slot: int):
        """Drop every block reference the slot holds (idempotent)."""
        for b in self.slot_blocks.pop(slot, []):
            self._drop(slot, b)

    def prepare_write(self, slot: int, pos: int
                      ) -> Tuple[List[Tuple[int, int]],
                                 Optional[Tuple[int, int, int]]]:
        """Make logical position ``pos`` of ``slot`` writable. Returns
        (new_allocs [(logical, phys), ...], cow (src, dst, logical) | None).
        Allocates missing blocks up to pos // bs; if the target block is
        shared or registered, copy-on-write swaps in a private copy (the
        caller must device-copy src -> dst)."""
        blocks = self.slot_blocks.setdefault(slot, [])
        lb = pos // self.block_size
        new: List[Tuple[int, int]] = []
        while len(blocks) <= lb:
            b = self._alloc_raw()
            self._take(slot, b)
            # _take appended; record the logical index it landed on
            new.append((len(blocks) - 1, b))
            self._log("page_alloc", slot, b)
        cow = None
        tgt = blocks[lb]
        if self.ref[tgt] > 1 or tgt in self.registered:
            dst = self._alloc_raw()
            self.ref[dst] += 1
            blocks[lb] = dst
            # drop the old reference WITHOUT the list append of _take
            self.ref[tgt] -= 1
            if self.ref[tgt] == 0 and tgt not in self.registered:
                self.free.append(tgt)
            elif self.ref[tgt] == 0:
                self._tick += 1
                self.lru[tgt] = self._tick
            cow = (tgt, dst, lb)
            self.cow_copies += 1
            self._log("page_cow", slot, (tgt, dst))
        self._bump_peak()
        return new, cow

    def pin(self, b: int):
        """Take a slot-less reference keeping ``b`` off the reclaim path —
        used for a pending copy-on-write SOURCE whose device copy is
        deferred to later in the same engine round (a same-round admission
        must not reclaim and overwrite it first). Logged as a share so
        event-replay refcounts stay balanced; ``audit`` must not run while
        pins are outstanding."""
        self.lru.pop(b, None)
        self.ref[b] += 1
        self._log("page_share", -1, b)

    def unpin(self, b: int):
        self._drop(-1, b)

    def mark_written(self, blocks=None):
        """The engine finished an admission round: every planned prefill
        (fresh and shared-tail) has executed, so blocks registered this
        round now hold real content and become prefix-matchable.
        ``blocks`` restricts the clear to one request's blocks (a chunked
        admission finishing its LAST chunk must not unblock other slots'
        still-unwritten pending blocks)."""
        if blocks is None:
            self.pending.clear()
        else:
            for b in blocks:
                self.pending.discard(b)

    def sleep(self):
        """Pool-wide sleep between serve() calls: drop the prefix registry
        and return every retained (refcount-0, LRU-cached) block to the
        free list, leaving occupancy at exactly zero. Only legal when no
        slot holds blocks — a live or leaked reference is a bug, not a
        cache to retain — and required before a weight push, since
        registered blocks hold KV activations of the OLD parameters."""
        n = self.blocks_in_use()
        if n:
            raise RuntimeError(
                f"pool sleep with {n} blocks still referenced "
                "(live or leaked slot state)")
        for b in list(self.registered):
            self._deregister(b)
            self.free.append(b)
        self.lru.clear()
        self.pending.clear()
        assert len(self.free) == self.num_blocks, \
            "pool sleep left blocks unaccounted for"
        self._log("pool_sleep", -1, None)

    def audit(self):
        """Allocator invariants; raises AssertionError on violation."""
        counts = np.zeros(self.num_blocks, np.int64)
        for slot, blocks in self.slot_blocks.items():
            for b in blocks:
                assert 0 <= b < self.num_blocks, (slot, b)
                counts[b] += 1
        assert (counts == self.ref).all(), "refcounts out of sync"
        free = set(self.free)
        assert len(free) == len(self.free), "duplicate blocks on free list"
        assert all(self.ref[b] == 0 for b in free), "free block still referenced"
        assert not (free & set(self.lru)), "block both free and cached"
        assert all(self.ref[b] == 0 for b in self.lru), "cached block referenced"
        # no aliased writable blocks: a block seen by >1 slot must be a
        # registered (immutable prefix) block — writes go through
        # prepare_write, which would have CoW'd it
        for b in np.nonzero(counts > 1)[0]:
            assert int(b) in self.registered, f"block {b} aliased but writable"


# ---------------------------------------------------------------------------
# Device helpers (pure jax; the engine jits them once per shape)
# ---------------------------------------------------------------------------

def _groups(cache) -> List[str]:
    return [g for g in PAGED_GROUPS if g in cache]


def scatter_prefill(paged_cache: PyTree, dense_cache: PyTree, admit_mask):
    """Splice a dense ragged-prefilled cache [L,B,T,KV,hd] into the pool
    [L,NB+1,KV,bs,hd] through the table (rows with admit_mask False write
    to the trash block — their live blocks and lengths are untouched). T
    may cover fewer logical blocks than nb_max; the rest stay
    decode-writable. This is the bitwise-exact admission path: the values
    written are the DENSE prefill's values, so a subsequent paged decode
    reads exactly what the dense engine would."""
    import jax.numpy as jnp
    table = paged_cache["table"]
    out = dict(paged_cache)
    for g in _groups(paged_cache):
        pool_k = paged_cache[g]["k"]
        trash = pool_k.shape[1] - 1
        bs = pool_k.shape[3]
        kd, vd = dense_cache[g]["k"], dense_cache[g]["v"]
        L, Bv, T, KV, hd = kd.shape
        nbp = T // bs
        # non-admitted rows (and an admitted row's unallocated tail
        # entries) DROP their writes out of bounds — scattering them into
        # the shared trash block would race between rows (duplicate scatter
        # indices have no defined winner) and the trash block must stay
        # all-zero for every read path that is masked against it
        tbl = jnp.where(admit_mask[:, None] & (table[:, :nbp] != trash),
                        table[:, :nbp], trash + 1)

        def blocks(x):                        # -> [L, Bv, nbp, KV, bs, hd]
            return jnp.swapaxes(x.reshape(L, Bv, nbp, bs, KV, hd), 3, 4)

        out[g] = {
            "k": pool_k.at[:, tbl].set(blocks(kd), mode="drop"),
            "v": paged_cache[g]["v"].at[:, tbl].set(blocks(vd), mode="drop"),
            "len": jnp.where(admit_mask[None, :], dense_cache[g]["len"],
                             paged_cache[g]["len"]),
        }
    return out


def copy_blocks(paged_cache: PyTree, src, dst):
    """Copy pool block src[i] -> dst[i] in every layer of every group
    (copy-on-write). Pad unused lanes with the trash index on both sides."""
    out = dict(paged_cache)
    for g in _groups(paged_cache):
        leaf = dict(paged_cache[g])
        for kv in ("k", "v"):
            pool = leaf[kv]
            leaf[kv] = pool.at[:, dst].set(pool[:, src])
        out[g] = leaf
    return out


def gather_slot(paged_cache: PyTree, row_table):
    """One slot's blocks, gathered to [L, nb, KV, bs, hd] per group (the
    sleep-level-1 offload payload; unallocated entries carry trash garbage
    that ``upload_slot`` never writes back)."""
    return {g: {"k": paged_cache[g]["k"][:, row_table],
                "v": paged_cache[g]["v"][:, row_table]}
            for g in _groups(paged_cache)}


def upload_slot(paged_cache: PyTree, payload: PyTree, idx, slot_mask,
                new_len):
    """Wake from sleep level 1: write payload blocks back at the freshly
    allocated physical slots ``idx`` [nb] (out-of-range = skip, used for the
    unallocated tail) and set the slot's per-layer length."""
    out = dict(paged_cache)
    for g in _groups(paged_cache):
        leaf = dict(paged_cache[g])
        for kv in ("k", "v"):
            leaf[kv] = leaf[kv].at[:, idx].set(payload[g][kv], mode="drop")
        ln = leaf["len"]
        leaf["len"] = jnp_where(slot_mask[None, :], new_len, ln)
        out[g] = leaf
    return out


def jnp_where(c, a, b):
    import jax.numpy as jnp
    return jnp.where(c, a, b)


def paged_row_health(cache: PyTree):
    """[B] bool — per-row finiteness of the row's OWN blocks (masked by the
    row's valid length; trash-backed and pad positions are ignored). The
    paged twin of resilience.row_health_fn — pool leaves have no batch axis,
    so health must be read through the table."""
    import jax.numpy as jnp
    table = cache["table"]
    B, nb = table.shape
    ok = jnp.ones((B,), bool)
    for g in _groups(cache):
        bs = cache[g]["k"].shape[3]
        ln = cache[g]["len"][0]                          # [B] (equal per layer)
        pos = jnp.arange(nb * bs).reshape(nb, bs)
        valid = pos[None] < ln[:, None, None]            # [B, nb, bs]
        m = valid[None, :, :, None, :, None]
        for kv in ("k", "v"):
            gathered = cache[g][kv][:, table]            # [L,B,nb,KV,bs,hd]
            fin = jnp.isfinite(gathered) | ~m
            ok &= jnp.all(fin, axis=(0, 2, 3, 4, 5))
    return ok


def paged_poison_rows(cache: PyTree, idx):
    """NaN-fill the physical pool blocks named by ``idx`` [B, nb] int32
    (the paged twin of resilience.poison_rows_fn; out-of-range entries
    drop). The engine passes only blocks EXCLUSIVELY owned by the poisoned
    rows — shared or registered blocks are copy-on-write swapped for
    private copies and dropped from the prefix registry first — so a
    poison_request fault can never corrupt a co-resident row sharing the
    prefix, and no NaN block ever lingers in ``by_hash``/``lru`` to serve
    a future prefix hit."""
    import jax.numpy as jnp
    out = dict(cache)
    for g in _groups(cache):
        leaf = dict(cache[g])
        pool_k = leaf["k"]
        nan_blk = jnp.full((pool_k.shape[0],) + idx.shape + pool_k.shape[2:],
                           jnp.nan, pool_k.dtype)
        for kv in ("k", "v"):
            leaf[kv] = leaf[kv].at[:, idx].set(nan_blk, mode="drop")
        out[g] = leaf
    return out
