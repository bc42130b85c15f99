"""ServeEngine: fused-prefill cache fill + batched decode with sampling.

The one serving code path: ``launch/serve.py`` is an argparse shim over
this class. Prefill is ONE full-sequence ``prefill_with_cache`` pass (the
blockwise/flash `prefill_attn` kernel op) that writes every layer's decode
state — not the old per-token teacher-forcing loop — and is timed so
prefill tok/s is a first-class serving metric alongside decode tok/s.

    spec = RunSpec(arch="stablelm-1.6b", reduced=True, host_devices=4)
    engine = ServeEngine(spec, batch=4, prompt_len=64, gen=32)
    result = engine.generate()
    print(result["prefill_tok_s"], result["decode_tok_s"])

Continuous batching (:meth:`serve`): a request queue plus an
iteration-level scheduler over ``max_slots`` fixed decode slots. Ragged
prompts prefill LEFT-ALIGNED with per-row cache lengths
(``batch["lengths"]`` through ``models.prefill_with_cache``), decode runs
ONE jitted ``decode_step(..., ragged=True)`` whose per-row slot writes let
every row sit at its own position, and a finished row's slot is re-filled
by splicing a freshly prefilled cache row into the live cache
(``engine.batching.merge_caches`` — no retrace). Per-row generation state
(step count, done bookkeeping, sampling key) lives in
``engine.batching.SlotScheduler`` + a [B] sampling-key batch.

For enc-dec archs the encoder runs through the public ``models.encode``
and the memory cache is the EXACT encoder output (shape follows the
encoder; no zeros-padded splice for cross-attention to leak onto).

Graceful degradation (:meth:`serve`): every request leaves with a terminal
``status`` ("ok" | "timeout" | "rejected" | "failed") and its partial
tokens — malformed requests are REJECTED at enqueue time, per-request
step-budget deadlines expire waiting or live requests as ``timeout``,
``queue_limit`` bounds the admission queue with explicit rejection, a
request whose cache rows go non-finite is QUARANTINED (evicted, status
"failed") without perturbing its co-residents, and an exhausted
``max_steps`` budget times the stragglers out instead of raising. The
``resilience=`` fault injector (``engine.resilience``) can poison a
request's cache rows to drive the quarantine path deterministically.

Paged serving (``paged=True``): the dense per-slot cache reservation is
replaced by ``engine.paging`` — a fixed pool of ``kv_pool_blocks`` KV
blocks of ``kv_block_size`` tokens with a per-slot block table. Admission
prefills into a TRANSIENT dense cache and block-scatters it through the
table (bitwise-identical numerics to the dense engine), prompts sharing a
cached prefix skip re-prefilling it (``prefix_cache``, copy-on-write on
divergence), and pool exhaustion preempts the newest request to host RAM
(``sleep_level`` 1: offload + bitwise wake; 2: discard + re-prefill).
Pool/prefix state PERSISTS across serve() calls, so a warmed engine serves
repeat prompts at a high prefix hit rate. Every terminal status — ok,
timeout, rejected, failed — releases the slot's blocks through one choke
point, so the pool can never leak from an eviction path.

Wall-clock serving (``serve(policy=batching.ServePolicy(...))``): the
nine historical serve() kwargs are deprecated aliases of ONE policy
dataclass, which additionally configures

* chunked prefill (``prefill_chunk=N``): each admitted prompt is cut into
  N-token chunks prefilled one per scheduler iteration, interleaved with
  the co-residents' decode steps — a long prompt no longer stalls every
  live stream for its whole prefill, and the emitted tokens stay BITWISE
  identical to whole-prompt admission (dense and paged);
* a clock mode ("step" | "wall" | "virtual"): seconds-denominated
  arrivals/deadlines (``Request.arrival_time``/``deadline_s``) with a
  deterministic virtual clock for tests and a StepWatchdog for slow-step
  reporting;
* pluggable admission ("fcfs" | "slo"): SLO admission is
  earliest-deadline-first with feasibility culling — doomed requests are
  left to expire in the queue instead of burning slots;
* streaming: ``Request.on_token`` / :meth:`ServeEngine.serve_stream`
  observe each emitted token from the SAME fused per-iteration host sync
  that serves the eos check and the quarantine health pass (one [B]-sized
  transfer per iteration, never one per concern).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

from repro.engine import batching
from repro.engine import resilience as rsl
from repro.engine.spec import RunSpec

PyTree = Any


def _sampler():
    """Per-row sampling closure shared by the dense and paged serving fns.
    ``temps``/``topks`` are [B] RUNTIME data (per-request overrides with
    the engine-wide default filled in host-side), so ONE jitted step serves
    a heterogeneous batch — rollout groups get per-request diversity
    without a retrace. A row with temp <= 0 takes argmax (same tokens the
    old engine-wide greedy path produced); temp > 0 samples categorically
    over the row's top-k logits (k <= 0 disables the truncation) with one
    key per row, so a request's stream never depends on its co-residents."""
    import jax
    import jax.numpy as jnp

    @jax.named_scope("sample")
    def sample(logits, keys, temps, topks):
        def one(k, lg, temp, tk):
            nk, sub = jax.random.split(k)
            lg32 = lg.astype(jnp.float32)
            vocab = lg32.shape[-1]
            kth = jnp.sort(lg32)[::-1][jnp.clip(tk, 1, vocab) - 1]
            masked = jnp.where((tk <= 0) | (lg32 >= kth), lg32, -jnp.inf)
            samp = jax.random.categorical(
                sub, masked / jnp.maximum(temp, 1e-6), -1)
            t = jnp.where(temp > 0, samp, jnp.argmax(lg, -1))
            return nk, t
        keys, toks = jax.vmap(one)(keys, logits, temps, topks)
        return toks.astype(jnp.int32), keys
    return sample


def _sid(req: "batching.Request") -> int:
    """The fold-in id for a request's sampling key stream: ``seed`` when
    set, else ``rid`` (the historical behaviour)."""
    return req.seed if req.seed is not None else req.rid


class ServeEngine:
    def __init__(self, spec: RunSpec, *,
                 batch: int = 4,
                 prompt_len: int = 64,
                 gen: int = 32,
                 cache_len: Optional[int] = None,
                 temperature: float = 0.0,
                 resilience=None,         # FaultInjector | spec str | None
                 paged: bool = False,
                 kv_block_size: int = 16,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 sleep_level: int = 1,
                 verbose: bool = True):
        spec.ensure_host_devices()
        self.spec = spec
        self.batch = batch
        self.prompt_len = prompt_len
        self.gen = gen
        self.temperature = temperature
        self.injector = rsl.FaultInjector.from_spec(resilience,
                                                    seed=spec.seed)
        self.events = rsl.EventLog()
        self.verbose = verbose
        self.paged = paged
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        self.prefix_cache = prefix_cache
        if sleep_level not in (1, 2):
            raise ValueError(f"sleep_level={sleep_level}; expected 1 "
                             "(offload to host RAM) or 2 (discard + "
                             "re-prefill on wake)")
        self.sleep_level = sleep_level
        if paged and kv_block_size < 1:
            raise ValueError(f"kv_block_size={kv_block_size} must be >= 1")

        self.cfg = spec.resolve_config()
        self.cache_len = cache_len or (prompt_len + gen)
        self.mesh = None
        self.params = None
        self.cache = None
        self._built = False
        self._warm = set()                # traced (fn, shapes) signatures
        self._serving = {}                # slot-count -> jitted serving fns
        self._cache_axes = None           # dense merge axes, once per build
        self._paged_state = None          # pool + device cache, persistent
        self._stream_cb = None            # serve_stream's per-token hook

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(msg, flush=True)

    # -- lifecycle ---------------------------------------------------------

    def build(self) -> "ServeEngine":
        if self._built:
            return self
        import jax
        from repro.models import init_params
        from repro.models import model as model_mod

        self.mesh = self.spec.build_mesh()
        self.params = init_params(self.cfg,
                                  jax.random.PRNGKey(self.spec.seed))
        cfg = self.cfg
        self._prefill_fn = jax.jit(
            lambda p, b, c: model_mod.prefill_with_cache(cfg, p, b, c))
        self._decode_fn = jax.jit(
            lambda p, b, c: model_mod.decode_step(cfg, p, b, c))
        if cfg.family == "encdec":
            self._encode_fn = jax.jit(
                lambda p, f: model_mod.encode(cfg, p, f))
        if self.paged:
            reason = model_mod.paged_unsupported_reason(cfg)
            if reason is not None:
                raise NotImplementedError(
                    f"paged KV cache unsupported: {reason}. Serve this "
                    "family with the dense merge_caches engine "
                    "(ServeEngine(..., paged=False)) instead.")
        self._built = True
        return self

    def _batch_axes(self, init_fn):
        """Per-leaf cache batch axes for ``batching.merge_caches``,
        discovered ONCE per engine build (eval_shape traces the whole cache
        pytree twice; re-running it for every slot count repaid that on
        every ``_serving_fns`` build). Fails fast naming both admission
        paths so an axis-ambiguous cache layout points at its options."""
        if self._cache_axes is None:
            try:
                self._cache_axes = batching.cache_batch_axes(init_fn)
            except ValueError as e:
                raise ValueError(
                    "cache batch-axis discovery failed for family "
                    f"{self.cfg.family!r}: {e}. The DENSE engine admits by "
                    "row-splicing with batching.merge_caches and needs "
                    "these axes; the PAGED engine (ServeEngine(..., "
                    "paged=True)) admits through the block table instead "
                    "and never calls merge_caches — but it only supports "
                    "families where models.paged_unsupported_reason(cfg) "
                    "is None.") from e
        return self._cache_axes

    def _warmup(self, tag, fn, *args):
        """Compile outside the timed region, once per argument-shape
        signature (the fns are pure — discarding outputs is side-effect
        free). Steady-state calls pay exactly one execution."""
        import jax
        sig = (tag, str(jax.tree.map(lambda x: getattr(x, "shape", None),
                                     args)))
        if sig not in self._warm:
            jax.block_until_ready(fn(*args))
            self._warm.add(sig)

    # -- public API --------------------------------------------------------

    def encode(self, frames):
        """Encoder memory for enc-dec archs (public — no private
        ``model._run_encoder`` reach-through)."""
        self.build()
        if self.cfg.family != "encdec":
            raise ValueError(
                f"encode() is for encdec archs, not {self.cfg.family!r}")
        return self._encode_fn(self.params, frames)

    def prefill(self, prompts, *, extras: Optional[Dict[str, Any]] = None):
        """Fill the decode cache from ``prompts`` [B, S] in one fused pass.

        ``extras`` carries the family side-inputs (``frames`` for enc-dec,
        ``patches`` for VLM); missing ones are synthesised as zeros so every
        arch serves out of the box. Returns the last-position logits and
        records prefill timing."""
        import jax
        import jax.numpy as jnp
        from repro.models import init_cache

        self.build()
        B, S = prompts.shape
        vlm_prefix = self.cfg.vlm.num_patches if self.cfg.vlm else 0
        cache = init_cache(self.cfg, B, self.cache_len + vlm_prefix)
        batch = {"tokens": jnp.asarray(prompts)}
        batch.update(extras or {})
        if self.cfg.family == "encdec" and "frames" not in batch:
            e = self.cfg.encdec
            batch["frames"] = jnp.zeros(
                (B, max(1, S // e.frame_rate_divisor), e.frontend_dim),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "vlm" and "patches" not in batch:
            v = self.cfg.vlm
            batch["patches"] = jnp.zeros((B, v.num_patches, v.vision_dim),
                                         jnp.dtype(self.cfg.dtype))

        # warm the jit cache first so the timed call measures execution,
        # not trace+compile (same methodology as benchmarks/decode_bench)
        self._warmup("prefill", self._prefill_fn, self.params, batch, cache)
        t0 = time.time()
        logits, self.cache = jax.block_until_ready(
            self._prefill_fn(self.params, batch, cache))
        self.prefill_s = time.time() - t0
        self.prefill_tok_s = B * S / max(self.prefill_s, 1e-9)
        return logits

    def decode(self, logits, n: Optional[int] = None):
        """Batched sampling loop from the prefilled cache. Greedy when
        temperature == 0, categorical otherwise. Returns tokens [B, n]."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        if self.cache is None:
            raise RuntimeError("call prefill() before decode()")
        n = self.gen if n is None else n
        key = jax.random.PRNGKey(self.spec.seed + 1)
        tok = jnp.argmax(logits, -1)
        # warm the decode compile outside the timed loop (decode_step is
        # pure — discarding the outputs leaves self.cache untouched)
        self._warmup("decode", self._decode_fn, self.params, {"token": tok},
                     self.cache)
        out = []
        t0 = time.time()
        for _ in range(n):
            # buffer DEVICE-side: np.asarray(tok) here would force a host
            # sync per token inside the timed loop
            out.append(tok)
            logits, self.cache = self._decode_fn(
                self.params, {"token": tok}, self.cache)
            if self.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits.astype(jnp.float32) / self.temperature, -1)
            else:
                tok = jnp.argmax(logits, -1)
        jax.block_until_ready(logits)
        self.decode_s = time.time() - t0
        self.decode_tok_s = len(out) * logits.shape[0] / max(self.decode_s, 1e-9)
        return np.asarray(jnp.stack(out, 1))     # ONE transfer, post-timing

    def generate(self, prompts=None,
                 extras: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """End-to-end: (synthetic) prompts -> fused prefill -> batched
        decode. ``extras`` forwards family side-inputs (frames/patches) to
        prefill. Returns tokens and both serving throughput metrics."""
        import jax.numpy as jnp
        from repro.data.synthetic import make_lm_data

        self.build()
        if prompts is None:
            toks = make_lm_data(self.cfg.vocab_size,
                                self.batch * self.prompt_len + 1,
                                seed=self.spec.seed)
            prompts = jnp.asarray(
                toks[:self.batch * self.prompt_len]
                .reshape(self.batch, self.prompt_len) % self.cfg.vocab_size)
        logits = self.prefill(prompts, extras=extras)
        tokens = self.decode(logits)
        B, S = prompts.shape
        self._log(
            f"prefill: {S} tokens x batch {B} in {self.prefill_s:.2f}s "
            f"({self.prefill_tok_s:.1f} tok/s); "
            f"decode: {tokens.shape[1]} tokens x batch {B} in "
            f"{self.decode_s:.2f}s ({self.decode_tok_s:.1f} tok/s)")
        return {"tokens": tokens, "prompts": prompts,
                "prefill_s": self.prefill_s,
                "prefill_tok_s": self.prefill_tok_s,
                "decode_s": self.decode_s,
                "decode_tok_s": self.decode_tok_s}

    # -- continuous batching ------------------------------------------------

    _SLOT_FAMILIES = ("dense", "moe", "vlm")

    def _serving_fns(self, n_slots: int):
        """Build (once per slot count) the two jitted serving functions:

        ``admit``  — ragged prefill of the admission batch into a FRESH
                     cache, per-row spliced into the live cache
                     (``merge_caches``), first token sampled per admitted
                     row, sampling keys re-seeded from the request id (so a
                     request's stream never depends on its co-residents);
        ``step``   — one ``decode_step(..., ragged=True)`` + per-row
                     sampling.

        Both are shape-static: every serve() call with the same slot count
        reuses the same executables — admission never retraces (sampling
        temperature / top-k are runtime [B] data, not trace constants)."""
        key = (n_slots, self.prompt_len, self.gen)
        if key in self._serving:
            return self._serving[key]
        import jax
        import jax.numpy as jnp
        from repro.models import init_cache
        from repro.models import model as model_mod

        cfg = self.cfg
        B, S_pad = n_slots, self.prompt_len
        cache_len = self.cache_len           # honor the constructor override
        if cache_len < S_pad + self.gen:
            raise ValueError(
                f"cache_len={cache_len} cannot hold prompt_len={S_pad} + "
                f"gen={self.gen} (a row would overflow its slot)")
        vlm_prefix = cfg.vlm.num_patches if cfg.vlm else 0
        init_fn = lambda b: init_cache(cfg, b, cache_len + vlm_prefix)
        axes = self._batch_axes(init_fn)
        base_key = jax.random.PRNGKey(self.spec.seed + 1)
        sample = _sampler()

        def admit(params, prompts, lengths, mask, rids, tok, cache, keys,
                  temps, topks):
            b = {"tokens": prompts, "lengths": lengths}
            if cfg.family == "vlm":
                v = cfg.vlm
                b["patches"] = jnp.zeros((B, v.num_patches, v.vision_dim),
                                         jnp.dtype(cfg.dtype))
            logits, filled = model_mod.prefill_with_cache(cfg, params, b,
                                                          init_fn(B))
            cache = batching.merge_caches(cache, filled, mask, axes)
            fresh_keys = jax.vmap(
                lambda r: jax.random.fold_in(base_key, r))(rids)
            keys = jnp.where(mask[:, None], fresh_keys, keys)
            tok0, keys2 = sample(logits, keys, temps, topks)
            keys = jnp.where(mask[:, None], keys2, keys)
            tok = jnp.where(mask, tok0, tok)
            return tok, cache, keys

        def admit_chunk(params, tails, lengths, hist, mask, rids, tok,
                        cache, keys, temps, topks):
            # chunked prefill directly on the LIVE dense cache: each row
            # advances its tail (absolute positions hist..lengths); rows
            # with (lengths, hist) = (len, len) carry an empty tail — no
            # writes, length preserved. ``mask`` marks rows landing their
            # FINAL chunk: only those re-seed their key stream and sample
            # their first token.
            b = {"tokens": tails, "lengths": lengths, "hist": hist}
            logits, cache = model_mod.prefill_with_cache(cfg, params, b,
                                                         cache)
            fresh_keys = jax.vmap(
                lambda r: jax.random.fold_in(base_key, r))(rids)
            keys = jnp.where(mask[:, None], fresh_keys, keys)
            tok0, keys2 = sample(logits, keys, temps, topks)
            keys = jnp.where(mask[:, None], keys2, keys)
            tok = jnp.where(mask, tok0, tok)
            return tok, cache, keys

        def step(params, tok, cache, keys, temps, topks):
            logits, cache = model_mod.decode_step(cfg, params, {"token": tok},
                                                  cache, ragged=True)
            tok, keys = sample(logits, keys, temps, topks)
            return tok, cache, keys

        def step_active(params, tok, cache, keys, temps, topks, active):
            # chunked-mode decode: rows with active=False (mid-prefill)
            # drop their cache write and keep their length frozen
            logits, cache = model_mod.decode_step(
                cfg, params, {"token": tok, "active": active}, cache,
                ragged=True)
            tok, keys = sample(logits, keys, temps, topks)
            return tok, cache, keys

        health_fn = rsl.row_health_fn(axes)

        def sync(tok, cache):
            # the fused per-iteration host readback: sampled tokens (eos /
            # streaming) and row health (quarantine) in ONE [2, B] transfer
            return jnp.stack([tok, health_fn(cache).astype(jnp.int32)])

        fns = {"admit": jax.jit(admit), "step": jax.jit(step),
               "admit_chunk": jax.jit(admit_chunk),
               "step_active": jax.jit(step_active),
               "sync": jax.jit(sync),
               "init": init_fn, "base_key": base_key, "axes": axes,
               # resilience pair: [B] row health + NaN row poisoning (the
               # quarantine detector and its chaos-test driver)
               "health": jax.jit(health_fn),
               "poison": jax.jit(rsl.poison_rows_fn(axes))}
        self._serving[key] = fns
        return fns

    def _paged_setup(self, n_slots: int) -> Dict[str, Any]:
        """The persistent paged-serving state: the BlockPool allocator, the
        device block-pool cache, and the host mirrors of the table and
        per-row lengths. Persisting it across serve() calls is what keeps
        the prefix cache warm; a changed slot count / pool geometry rebuilds
        it (and drops the cached prefixes)."""
        import numpy as np
        from repro.engine import paging
        from repro.models import model as model_mod

        bs = self.kv_block_size
        cache_len_p = paging.round_up(self.cache_len, bs)
        nb_max = cache_len_p // bs
        pool_blocks = self.kv_pool_blocks or n_slots * nb_max
        st = self._paged_state
        if st is not None and (st["B"], st["bs"], st["pool_blocks"]) == \
                (n_slots, bs, pool_blocks):
            if st["cache"] is None:     # woken from pool_sleep(level=2)
                st["cache"] = model_mod.init_paged_cache(
                    self.cfg, n_slots, pool_blocks, bs, cache_len_p)
            return st
        if st is not None:
            self._log("paged: pool geometry changed — rebuilding the block "
                      "pool (cached prefixes dropped)")
        pool = paging.BlockPool(pool_blocks, bs,
                                prefix_cache=self.prefix_cache)
        cache = model_mod.init_paged_cache(self.cfg, n_slots, pool_blocks,
                                           bs, cache_len_p)
        st = {"B": n_slots, "bs": bs, "nb_max": nb_max,
              "pool_blocks": pool_blocks, "pool": pool, "cache": cache,
              "table": np.full((n_slots, nb_max), pool_blocks, np.int32),
              "row_len": np.zeros((n_slots,), np.int64)}
        self._paged_state = st
        return st

    def pool_sleep(self, level: int = 2) -> None:
        """Put the persistent paged-serving state to sleep between serve()
        calls. Level 1 drops the prefix registry (occupancy goes to zero;
        the device KV arrays stay allocated); level 2 additionally FREES
        the device pool cache, so during a rollout train phase KV memory
        and optimizer state never coexist at peak — the next serve() call
        re-allocates the pool and re-prefills. Either level invalidates
        every cached prefix, which is also a correctness requirement after
        a weight push: registered blocks hold KV activations of the OLD
        parameters. No-op when no paged state exists yet."""
        if level not in (1, 2):
            raise ValueError(f"pool_sleep level={level}; expected 1 or 2")
        st = self._paged_state
        if st is None:
            return
        st["pool"].sleep()
        st["table"][:] = st["pool_blocks"]
        st["row_len"][:] = 0
        if level == 2:
            st["cache"] = None
        self.events.append("pool_sleep", 0, level=level,
                           pool_blocks=st["pool_blocks"])

    def _serving_fns_paged(self, n_slots: int, nb_max: int):
        """Paged twins of ``_serving_fns`` (built once per slot count):

        ``admit_fresh``  — ragged prefill of admissions with NO cached
                           prefix into a TRANSIENT dense cache of
                           round_up(S, block) positions, block-scattered
                           into the pool through the table
                           (``paging.scatter_prefill``). The prefill
                           numerics are the dense engine's — this path is
                           bitwise-identical to dense serving. Retraces
                           once per prompt width (normal admissions at
                           prompt_len; sleep-level-2 wakes at
                           prompt_len + gen).
        ``admit_shared`` — prefix-cache hits: prefill only the ragged TAIL
                           (positions hist..len) through the model's paged
                           prefill; the shared prefix is read from already
                           written (refcounted) blocks.
        ``step``         — one decode step; the block table rides inside
                           the cache pytree.
        ``gather/wake/copy`` — offload payload readout, sleep-level-1
                           restore, and the CoW block copy.
        ``health/poison``  — paged twins of the resilience pair (pool
                           leaves have no batch axis, so the dense
                           axes-based fns cannot see rows)."""
        key = ("paged", n_slots, self.prompt_len, self.gen,
               self.kv_block_size, nb_max)
        if key in self._serving:
            return self._serving[key]
        import jax
        import jax.numpy as jnp
        from repro.engine import paging
        from repro.models import init_cache
        from repro.models import model as model_mod

        cfg = self.cfg
        B, bs = n_slots, self.kv_block_size
        base_key = jax.random.PRNGKey(self.spec.seed + 1)
        sample = _sampler()

        def resample(logits, mask, rids, tok, keys, temps, topks):
            fresh_keys = jax.vmap(
                lambda r: jax.random.fold_in(base_key, r))(rids)
            keys = jnp.where(mask[:, None], fresh_keys, keys)
            tok0, keys2 = sample(logits, keys, temps, topks)
            keys = jnp.where(mask[:, None], keys2, keys)
            tok = jnp.where(mask, tok0, tok)
            return tok, keys

        def admit_fresh(params, prompts, lengths, mask, rids, tok, cache,
                        keys, temps, topks):
            S = prompts.shape[1]
            dense = init_cache(cfg, B, paging.round_up(S, bs))
            b = {"tokens": prompts, "lengths": lengths}
            logits, filled = model_mod.prefill_with_cache(cfg, params, b,
                                                          dense)
            cache = paging.scatter_prefill(cache, filled, mask)
            tok, keys = resample(logits, mask, rids, tok, keys, temps, topks)
            return tok, cache, keys

        def admit_shared(params, tails, lengths, hist, mask, rids, tok,
                         cache, keys, temps, topks):
            # non-admitted rows carry (lengths, hist) = (cur_len, cur_len)
            # — empty tail, every write trash-redirected, length preserved
            b = {"tokens": tails, "lengths": lengths, "hist": hist}
            logits, cache = model_mod.prefill_with_cache(cfg, params, b,
                                                         cache)
            tok, keys = resample(logits, mask, rids, tok, keys, temps, topks)
            return tok, cache, keys

        def step(params, tok, cache, keys, temps, topks):
            logits, cache = model_mod.decode_step(cfg, params,
                                                  {"token": tok}, cache,
                                                  ragged=True)
            tok, keys = sample(logits, keys, temps, topks)
            return tok, cache, keys

        def step_active(params, tok, cache, keys, temps, topks, active):
            # chunked-mode decode: inactive (mid-prefill) rows write to the
            # trash block and keep their length frozen
            logits, cache = model_mod.decode_step(
                cfg, params, {"token": tok, "active": active}, cache,
                ragged=True)
            tok, keys = sample(logits, keys, temps, topks)
            return tok, cache, keys

        def sync(tok, cache):
            # fused host readback: tokens + row health in ONE [2, B] pull
            return jnp.stack(
                [tok, paging.paged_row_health(cache).astype(jnp.int32)])

        def wake(cache, payload, idx, slot_mask, new_len, tok, last_tok,
                 keys, key_row):
            cache = paging.upload_slot(cache, payload, idx, slot_mask,
                                       new_len)
            tok = jnp.where(slot_mask, last_tok, tok)
            keys = jnp.where(slot_mask[:, None], key_row[None, :], keys)
            return cache, tok, keys

        fns = {"admit_fresh": jax.jit(admit_fresh),
               "admit_shared": jax.jit(admit_shared),
               "step": jax.jit(step),
               "step_active": jax.jit(step_active),
               "sync": jax.jit(sync),
               "gather": jax.jit(paging.gather_slot),
               "wake": jax.jit(wake),
               "copy": jax.jit(paging.copy_blocks),
               "health": jax.jit(paging.paged_row_health),
               "poison": jax.jit(paging.paged_poison_rows),
               "base_key": base_key}
        self._serving[key] = fns
        return fns

    def _reject(self, req: batching.Request, why: str) -> None:
        import numpy as np
        req.status = "rejected"
        req.error = why
        req.tokens = np.zeros((0,), np.int32)
        self.events.append("reject", req.arrival_step, rid=req.rid,
                           reason=why)
        self._log(f"request {req.rid} rejected: {why}")

    def _validate_requests(self, requests, S_pad):
        """Enqueue-time validation: a malformed request is REJECTED with a
        per-request error instead of failing the whole batch mid-loop.
        Returns the accepted requests."""
        accepted, seen = [], set()
        for r in requests:
            if r.rid in seen:
                self._reject(r, f"duplicate rid {r.rid}")
                continue
            seen.add(r.rid)
            if len(r.prompt) > S_pad or len(r.prompt) < 1:
                self._reject(r, f"prompt length {len(r.prompt)} not in "
                                f"[1, prompt_len={S_pad}]")
                continue
            if r.max_gen > self.gen or r.max_gen < 1:
                self._reject(r, f"max_gen {r.max_gen} not in "
                                f"[1, gen={self.gen}]")
                continue
            if r.deadline_steps is not None and r.deadline_steps < 1:
                self._reject(r, f"deadline_steps {r.deadline_steps} < 1")
                continue
            r.status = "queued"
            accepted.append(r)
        return accepted

    def serve(self, requests: Optional[List[batching.Request]] = None, *,
              policy: Any = None, **legacy) -> Dict[str, Any]:
        """Serve a request queue with iteration-level (continuous) batching.

        Configuration is ONE object: ``serve(policy=batching.ServePolicy(
        ...))``. The nine historical kwargs (``max_slots`` /
        ``num_requests`` / ``arrival`` / ``rate`` / ``eos_id`` /
        ``policy`` (str) / ``deadline_steps`` / ``queue_limit`` /
        ``max_steps``) remain as deprecated aliases: passing any of them
        resolves through ``batching.serve_policy_from_legacy_kwargs`` with
        ONE DeprecationWarning naming the kwargs to migrate.

        ``requests``: list of ``batching.Request``; None synthesises a
        staggered workload of ``policy.num_requests`` with the given
        ``policy.arrival`` trace ("none" | "poisson" at ``policy.rate``
        requests per decode step).

        ``ServePolicy.policy="continuous"`` admits into any freed slot the
        moment a row finishes; ``"static"`` is the fixed-batch baseline (a
        new batch is admitted only when EVERY slot is free) — same jitted
        functions, so the two are directly comparable. Beyond the
        historical step-clock behaviour, the policy adds:

        * ``prefill_chunk=N`` — chunked prefill: each admitted prompt is
          cut into N-token chunks prefilled one per scheduler iteration,
          interleaved with the co-residents' decode steps. A mid-prefill
          request has status "prefilling" and emits nothing; its token
          stream is BITWISE identical to whole-prompt admission (dense and
          paged — the paged path scatters each chunk into its blocks as it
          lands, and prefix-cache hits skip straight to the first cold
          chunk).
        * ``clock`` — "step" (the historical unit clock), "wall"
          (``time.monotonic`` seconds) or "virtual" (deterministic
          seconds, ``t * step_dt``). Seconds clocks honor
          ``Request.arrival_time`` / ``Request.deadline_s`` and
          ``ServePolicy.deadline_s``; ``watchdog_s`` arms a resilience
          ``StepWatchdog`` around each decode step and logs "slow_step"
          events (it blocks on the step's results, trading async dispatch
          for a truthful per-step latency verdict).
        * ``admission`` — "fcfs" (historical) | "slo" (earliest-deadline-
          first with feasibility culling) | any
          ``batching.AdmissionPolicy`` instance, reading queue depth and
          the run's timeout/reject counts from the admission context.
        * streaming — ``Request.on_token(rid, token, step, wall_t)`` fires
          per emitted token from the fused per-iteration host sync (the
          same single transfer that serves the eos check and the
          quarantine health pass); see :meth:`serve_stream`.

        Degradation contract (unchanged): serve() NEVER raises for a
        per-request failure. A malformed request is rejected at enqueue
        time (``status="rejected"``); deadlines expire a request — waiting
        or live — as ``status="timeout"`` with its partial tokens;
        ``queue_limit`` bounds the admission queue with explicit rejection
        at arrival; a request whose cache rows go non-finite is
        quarantined (``status="failed"``) with its co-residents bitwise
        unaffected; an exhausted ``max_steps`` budget times out every
        unfinished request instead of discarding them. Everything that
        completes normally returns ``status="ok"``.

        Returns the requests (``tokens`` + ``status`` filled), the
        scheduler event log, and throughput/latency/TTFT/goodput metrics
        (p50/p99 over requests that produced tokens)."""
        if isinstance(policy, batching.ServePolicy):
            if legacy:
                raise TypeError(
                    "serve(policy=ServePolicy(...)) does not combine with "
                    f"the deprecated kwargs {sorted(legacy)} — set those "
                    "fields on the ServePolicy instead")
            sp = policy
        else:
            if policy is not None:
                legacy["policy"] = policy
            sp = batching.serve_policy_from_legacy_kwargs(**legacy)
        return self._serve_impl(requests, sp)

    def serve_stream(self, requests: Optional[List[batching.Request]] = None,
                     *, policy: Any = None, **legacy):
        """Run :meth:`serve` on a background thread and yield ``(rid,
        token)`` pairs live, in emission order (the launcher's
        ``--stream`` path). The generator's return value
        (``StopIteration.value``) is serve()'s full result dict.

        Greedy rows are bitwise identical with or without streaming: the
        hook only OBSERVES the fused per-iteration host copy of the
        sampled tokens — it adds no device transfer and feeds nothing back
        into the jitted fns."""
        import queue
        import threading

        q: "queue.Queue" = queue.Queue()
        sentinel = object()
        box: Dict[str, Any] = {}

        def run():
            prev = self._stream_cb
            try:
                self._stream_cb = lambda rid, tok, step, wt: q.put((rid,
                                                                    tok))
                box["result"] = self.serve(requests, policy=policy,
                                           **legacy)
            except BaseException as e:       # surfaced to the consumer
                box["error"] = e
            finally:
                self._stream_cb = prev
                q.put(sentinel)

        th = threading.Thread(target=run, daemon=True)
        th.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        th.join()
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _serve_impl(self, requests: Optional[List[batching.Request]],
                    sp: "batching.ServePolicy") -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from jax.profiler import TraceAnnotation as span

        self.build()
        policy, eos_id = sp.policy, sp.eos_id
        deadline_steps, queue_limit = sp.deadline_steps, sp.queue_limit
        max_steps, prefill_chunk = sp.max_steps, sp.prefill_chunk
        clock, step_dt = sp.clock, sp.step_dt
        if self.cfg.family not in self._SLOT_FAMILIES:
            raise NotImplementedError(
                f"continuous batching serves attention-cache families "
                f"{self._SLOT_FAMILIES}, not {self.cfg.family!r} (a "
                f"recurrent prefill state would absorb ragged pad tails)")
        if eos_id is not None and not (0 <= eos_id < self.cfg.vocab_size):
            raise ValueError(
                f"eos_id={eos_id} outside the vocab [0, "
                f"{self.cfg.vocab_size}) — no request could ever emit it")
        if prefill_chunk:
            if self.cfg.family not in ("dense", "moe"):
                raise NotImplementedError(
                    f"chunked prefill supports dense/moe decoder stacks, "
                    f"not {self.cfg.family!r} (the VLM patch prefix is "
                    "prefilled in one piece)")
            if self.cfg.attn_window:
                raise NotImplementedError(
                    f"chunked prefill with attn_window="
                    f"{self.cfg.attn_window}: ring-buffer windows prefill "
                    "whole-prompt")
        B = sp.max_slots or self.batch
        S_pad = self.prompt_len
        if requests is None:
            requests = batching.synthetic_requests(
                sp.num_requests, self.cfg.vocab_size, S_pad, self.gen,
                arrival=sp.arrival, rate=sp.rate, seed=self.spec.seed)
        if not requests:
            raise ValueError("serve() needs at least one request")
        accepted = self._validate_requests(requests, S_pad)
        # the health/quarantine pass costs one [B]-bool transfer per step,
        # so it only runs when chaos is possible (an injector is armed);
        # the machinery itself is always compiled in
        guard = self.injector is not None

        from repro.engine import paging

        paged = self.paged
        sched = batching.SlotScheduler(B)
        if paged:
            if self.cache_len < S_pad + self.gen:
                raise ValueError(
                    f"cache_len={self.cache_len} cannot hold "
                    f"prompt_len={S_pad} + gen={self.gen} (a row would "
                    f"overflow its block table)")
            st = self._paged_setup(B)
            pool, bs = st["pool"], st["bs"]
            nb_max, trash = st["nb_max"], st["pool_blocks"]
            pool.events = sched.events    # allocator log -> event replay
            pool.reset_stats()
            fns = self._serving_fns_paged(B, nb_max)
            cache = st["cache"]
            # NB: every host->device transfer of st["table"] goes through a
            # .copy() — jnp.asarray's transfer is ASYNC, and the scheduler
            # mutates st["table"] in place; handing jax the live buffer
            # races the copy against the next mutation (reads of a FUTURE
            # table: wrong/unallocated blocks, nondeterministic tokens)
            cache["table"] = jnp.asarray(st["table"].copy())
            row_len = st["row_len"]
            # a request that could never fit the pool even ALONE must be
            # rejected up front — admission would otherwise retry forever
            fits = []
            for r in accepted:
                need = -(-(len(r.prompt) + r.max_gen) // bs)
                if need > pool.num_blocks:
                    self._reject(r, f"needs {need} KV blocks > pool of "
                                    f"{pool.num_blocks}")
                else:
                    fits.append(r)
            accepted = fits
        else:
            fns = self._serving_fns(B)

        # -- clock machinery -------------------------------------------------
        # "step" counts scheduler iterations (the historical unit clock —
        # bitwise-stable for every existing test); "virtual" is the SAME
        # deterministic schedule denominated in seconds (t * step_dt);
        # "wall" reads time.monotonic. All arrival/deadline comparisons go
        # through these three helpers, so the step-clock arithmetic is
        # numerically identical to the historical integer comparisons.
        adm = batching.resolve_admission(sp.admission)
        scale = 1.0 if clock == "step" else step_dt
        unit = "steps" if clock == "step" else "s"
        _mono0 = time.monotonic()

        def clock_now():
            if clock == "wall":
                return time.monotonic() - _mono0
            return t * scale

        def arr_of(r):
            if clock != "step" and r.arrival_time is not None:
                return r.arrival_time
            return r.arrival_step * scale

        def ddl_of(r):
            """Relative deadline of ``r`` in clock units (None = none)."""
            if clock != "step" and r.deadline_s is not None:
                return r.deadline_s
            if r.deadline_steps is not None:
                return r.deadline_steps * scale
            if clock != "step" and sp.deadline_s is not None:
                return sp.deadline_s
            if deadline_steps is not None:
                return deadline_steps * scale
            return None

        timeouts_ct = rejects_ct = 0

        def admission_order(free_ct):
            """The waiting queue as the admission policy orders (and
            possibly culls) it; FCFS short-circuits to the queue itself —
            the historical behaviour, no context construction per step."""
            if type(adm) is batching.FCFSAdmission:
                return list(waiting)
            ctx = batching.AdmissionContext(
                step=t, now=cnow, free_slots=free_ct,
                queue_depth=len(waiting), prefill_chunk=prefill_chunk,
                default_deadline=(deadline_steps * scale
                                  if deadline_steps is not None else None),
                timeouts=timeouts_ct, rejects=rejects_ct, step_dt=scale,
                deadline_fn=lambda r: (
                    None if ddl_of(r) is None else arr_of(r) + ddl_of(r)))
            return adm.select(list(waiting), ctx)

        wd = rsl.StepWatchdog(sp.watchdog_s) if sp.watchdog_s else None
        # streaming hooks observe the fused host sync — their presence (or
        # eos / an armed injector) is what turns that sync on at all
        stream_hooks = (self._stream_cb is not None or
                        any(r.on_token is not None for r in accepted))
        need_sync = guard or stream_hooks or eos_id is not None

        pending = sorted(accepted, key=lambda r: (arr_of(r), r.rid))
        waiting: List[batching.Request] = []
        parked: Dict[int, paging.Parked] = {}
        tok = jnp.zeros((B,), jnp.int32)
        if not paged:
            cache = fns["init"](B)
        keys = jax.vmap(lambda i: jax.random.fold_in(fns["base_key"], i))(
            jnp.arange(B))
        # per-slot sampling controls (Request.temperature / Request.top_k
        # overrides with the engine-wide defaults) — RUNTIME [B] data fed
        # to the jitted fns, so a heterogeneous batch never retraces. The
        # .copy() before upload mirrors the table convention: jnp.asarray
        # transfers asynchronously and the host rows mutate in place.
        temp_row = np.full((B,), self.temperature, np.float32)
        topk_row = np.zeros((B,), np.int32)

        def samp():
            return jnp.asarray(temp_row.copy()), jnp.asarray(topk_row.copy())

        def set_sampling(slot, req):
            temp_row[slot] = (self.temperature if req.temperature is None
                              else req.temperature)
            topk_row[slot] = req.top_k or 0

        # compile the serving fns outside the timed loop
        zp = jnp.zeros((B, S_pad), jnp.int32)
        zl = jnp.ones((B,), jnp.int32)
        zm = jnp.zeros((B,), bool)
        zr = jnp.zeros((B,), jnp.int32)
        if paged:
            self._warmup(("serve_admit_fresh", B), fns["admit_fresh"],
                         self.params, zp, zl, zm, zr, tok, cache, keys,
                         *samp())
            if self.prefix_cache:
                self._warmup(("serve_admit_shared", B), fns["admit_shared"],
                             self.params, zp, jnp.zeros((B,), jnp.int32),
                             jnp.zeros((B,), jnp.int32), zm, zr, tok, cache,
                             keys, *samp())
        else:
            self._warmup(("serve_admit", B), fns["admit"], self.params, zp,
                         zl, zm, zr, tok, cache, keys, *samp())
        if prefill_chunk:
            ztail = jnp.zeros((B, prefill_chunk), jnp.int32)
            zi = jnp.zeros((B,), jnp.int32)
            chunk_fn = fns["admit_shared"] if paged else fns["admit_chunk"]
            self._warmup(("serve_chunk", B, prefill_chunk), chunk_fn,
                         self.params, ztail, zi, zi, zm, zr, tok, cache,
                         keys, *samp())
            self._warmup(("serve_step_active", B), fns["step_active"],
                         self.params, tok, cache, keys, *samp(),
                         jnp.ones((B,), bool))
        else:
            self._warmup(("serve_step", B), fns["step"], self.params, tok,
                         cache, keys, *samp())
        if guard:
            self._warmup(("serve_sync", B), fns["sync"], tok, cache)
        preemptions = offloads = wakes = 0
        host_syncs = emission_iters = 0
        first_emit: Dict[int, float] = {}   # rid -> clock time of 1st token
        # chunked-prefill jobs: slot -> {req, prompt, off, hist0, blocks,
        # poison}; one chunk per job advances per scheduler iteration
        prefill_jobs: Dict[int, Dict[str, Any]] = {}
        # dense chunked mode tracks every row's device cache length on the
        # host (chunk calls must pass passenger rows their EXACT length);
        # decode increments all active rows, chunks set their row
        dense_len = np.zeros((B,), np.int64)

        def release_slot_resources(slot, upload=True):
            """THE terminal choke point: every path that frees a slot —
            completion, deadline eviction, quarantine, truncation,
            preemption — funnels through here, so the paged pool can never
            leak blocks from an exit path. Dense mode has no per-slot
            resources beyond the scheduler's own bookkeeping.
            ``upload=False`` defers the host->device table refresh so a
            loop releasing several slots can upload once afterwards."""
            temp_row[slot] = self.temperature
            topk_row[slot] = 0
            prefill_jobs.pop(slot, None)
            if paged:
                pool.release_slot(slot)
                st["table"][slot] = trash
                row_len[slot] = 0
                if upload:
                    cache["table"] = jnp.asarray(st["table"].copy())

        def refresh_row(slot):
            blocks = pool.slot_blocks.get(slot, [])
            st["table"][slot] = trash
            st["table"][slot, :len(blocks)] = blocks

        def do_cow(pairs):
            nonlocal cache
            if pairs:
                src = np.full((B,), trash, np.int32)
                dst = np.full((B,), trash, np.int32)
                for i, (s, d) in enumerate(pairs):
                    src[i], dst[i] = s, d
                cache = fns["copy"](cache, jnp.asarray(src),
                                    jnp.asarray(dst))

        def park(slot, why):
            """Preempt the slot's request to host RAM. Sleep level 1 keeps
            a bitwise payload of its blocks (wake = upload + resume); level
            2 keeps only the generated token values (wake = re-prefill).
            The pending sampled token is NOT yet in the history, so on wake
            it is re-injected (level 1) or re-derived (level 2)."""
            nonlocal preemptions, offloads
            if slot in sched.prefilling:
                # a mid-prefill victim has no sampled token to re-inject
                # and its cache row is half-filled — park at level-2
                # semantics regardless of sleep_level: keep only the
                # prompt, re-chunk from scratch on wake
                prefill_jobs.pop(slot, None)
                rid = sched.preempt(slot, t)
                parked[rid] = paging.Parked(rid=rid, level=2, n_tokens=0,
                                            generated=[])
                preemptions += 1
                pool._log("page_drop", slot, rid)
                release_slot_resources(slot)
                self.events.append("preempt", t, rid=rid, slot=slot,
                                   level=2, reason=why)
                self._log(f"step {t}: mid-prefill request {rid} preempted "
                          f"from slot {slot} (level 2: {why})")
                return
            rid = sched.preempt(slot, t)
            p = paging.Parked(rid=rid, level=self.sleep_level,
                              n_tokens=int(row_len[slot]), generated=[])
            if self.sleep_level == 1:
                payload = fns["gather"](cache,
                                        jnp.asarray(st["table"][slot].copy()))
                p.payload = jax.tree.map(np.asarray, payload)
                p.last_token = int(np.asarray(tok)[slot])
                p.key_row = np.asarray(keys)[slot]
                offloads += 1
                pool._log("page_offload", slot, rid)
            else:
                # the wake re-prefills prompt + generated, so only the
                # token VALUES survive; this is the rare path, so the host
                # sync of the slot's history rows is acceptable
                for h, s, c in sched.token_segments(rid):
                    if c:
                        seg = np.asarray(jnp.stack(history[h:h + c]))[:, s]
                        p.generated.extend(int(x) for x in seg)
                pool._log("page_drop", slot, rid)
            parked[rid] = p
            preemptions += 1
            release_slot_resources(slot)
            self.events.append("preempt", t, rid=rid, slot=slot,
                               level=self.sleep_level, reason=why)
            self._log(f"step {t}: request {rid} preempted from slot {slot} "
                      f"to host RAM (sleep level {self.sleep_level}: {why})")

        def try_wake_level1(p) -> bool:
            nonlocal cache, tok, keys, wakes
            free_now = sched.free_slots()
            if not free_now:
                return False
            slot = free_now[0]
            try:
                pool.prepare_write(slot, max(p.n_tokens - 1, 0))
            except paging.PoolExhausted:
                pool.release_slot(slot)   # roll back the partial grab
                return False
            sched.admit(slot, sched.requests[p.rid], t, len(history),
                        resume=True)
            set_sampling(slot, sched.requests[p.rid])
            refresh_row(slot)
            row_len[slot] = p.n_tokens
            cache["table"] = jnp.asarray(st["table"].copy())
            nblk = -(-p.n_tokens // bs)
            idx = np.full((nb_max,), trash + 1, np.int32)   # OOB -> drop
            idx[:nblk] = st["table"][slot, :nblk]
            mask1 = np.zeros((B,), bool)
            mask1[slot] = True
            cache, tok, keys = fns["wake"](
                cache, jax.tree.map(jnp.asarray, p.payload),
                jnp.asarray(idx), jnp.asarray(mask1),
                jnp.int32(p.n_tokens), tok, jnp.int32(p.last_token), keys,
                jnp.asarray(p.key_row))
            wakes += 1
            pool._log("page_wake", slot, p.rid)
            self.events.append("wake", t, rid=p.rid, slot=slot, level=1)
            self._log(f"step {t}: request {p.rid} woken into slot {slot} "
                      f"(level 1: {p.n_tokens} cached tokens restored)")
            return True

        def lifo_victim():
            live = sched.live_slots()
            if not live:
                return None
            # prefer a victim with tokens to park over a mid-prefill row
            # (whose park drops all its prefill work)
            pool_ = [s for s in live if s not in sched.prefilling] or live
            return max(pool_,
                       key=lambda s: (sched.admit_step[sched.owner[s]], s))

        def quarantine(health, now):
            """Evict live rows whose cache went non-finite (``health`` is
            this iteration's fused host sync verdict). Rows are
            independent across the batch axis, so a NaN row cannot perturb
            its co-residents — the quarantine just frees the slot and
            reports the failure instead of serving garbage."""
            for slot in sched.live_slots():
                if not health[slot]:
                    rid = sched.evict(slot, t, now, "failed")
                    sched.requests[rid].status = "failed"
                    sched.requests[rid].error = ("non-finite cache rows "
                                                 "(quarantined)")
                    release_slot_resources(slot)
                    self.events.append("quarantine", t, rid=rid, slot=slot)
                    self._log(f"step {t}: request {rid} quarantined "
                              f"(non-finite cache rows)")

        def paged_poison(slots):
            """Quarantine isolation for the paged injector: give each
            poisoned row a PRIVATE copy of every block it shares (or has
            registered for future sharing) before the NaN fill — the whole
            block is NaN'd anyway, so the CoW needs no device copy — and
            fill only blocks the row exclusively owns. Co-resident rows
            and the prefix registry never see the poison. If the pool
            cannot supply a private copy, the shared block is left intact
            (un-poisoned) rather than corrupting its other readers."""
            nonlocal cache
            idx = np.full((B, nb_max), trash + 1, np.int32)
            for slot in slots:
                nblk = len(pool.slot_blocks.get(slot, []))
                for lb in range(nblk):
                    try:
                        pool.prepare_write(slot, lb * bs)
                    except paging.PoolExhausted:
                        break
                for lb, b in enumerate(pool.slot_blocks.get(slot, [])):
                    if pool.ref[b] == 1 and b not in pool.registered:
                        idx[slot, lb] = b
                refresh_row(slot)
            cache["table"] = jnp.asarray(st["table"].copy())
            cache = fns["poison"](cache, jnp.asarray(idx))

        history: List[Any] = []          # device [B] token vectors
        owners_log: List[np.ndarray] = []
        arrival_wall: Dict[int, float] = {}
        t = 0
        decode_steps = prefill_calls = admitted_mid_decode = 0
        truncated = False
        t_start = time.perf_counter()
        while pending or waiting or parked or sched.live_slots():
            if t >= max_steps:
                truncated = True         # graceful: time the stragglers
                break                    # out below instead of raising
            with span("serve.iter"):
                now = time.perf_counter()
                cnow = clock_now()
                if paged:
                    pool.step = t            # stamp allocator events
                # -- arrivals (bounded admission queue) -----------------------
                with span("serve.arrivals"):
                    n_arrived = 0
                    for r in pending:
                        if arr_of(r) > cnow:
                            break                # pending is sorted by arrival
                        n_arrived += 1
                        arrival_wall.setdefault(r.rid, now)
                        if queue_limit is not None and \
                                len(waiting) >= queue_limit:
                            self._reject(r, f"admission queue full "
                                            f"(queue_limit={queue_limit})")
                            rejects_ct += 1
                        else:
                            waiting.append(r)
                    pending = pending[n_arrived:]
                    # -- deadline expiry (waiting, then live) -----------------
                    still = []
                    for r in waiting:
                        d = ddl_of(r)
                        if d is not None and cnow - arr_of(r) >= d:
                            r.status = "timeout"
                            r.error = (f"deadline of {d:g} {unit} expired "
                                       f"in queue")
                            r.tokens = np.zeros((0,), np.int32)
                            self.events.append("timeout", t, rid=r.rid,
                                               where="queue")
                            timeouts_ct += 1
                        else:
                            still.append(r)
                    waiting = still
                    for slot in sched.live_slots():
                        r = sched.requests[sched.owner[slot]]
                        d = ddl_of(r)
                        if d is not None and cnow - arr_of(r) >= d:
                            rid = sched.evict(slot, t, now, "timeout")
                            sched.requests[rid].status = "timeout"
                            sched.requests[rid].error = (
                                f"deadline of {d:g} {unit} expired "
                                f"mid-decode")
                            release_slot_resources(slot)
                            self.events.append("timeout", t, rid=rid,
                                               where="slot")
                            timeouts_ct += 1
                    for rid in list(parked):
                        r = sched.requests[rid]
                        d = ddl_of(r)
                        if d is not None and cnow - arr_of(r) >= d:
                            parked.pop(rid)      # payload dropped with it
                            r.status = "timeout"
                            r.error = (f"deadline of {d:g} {unit} expired "
                                       f"while parked")
                            sched.close(rid, t, now, "timeout")
                            self.events.append("timeout", t, rid=rid,
                                               where="parked")
                            timeouts_ct += 1
                # -- admissions -----------------------------------------------
                with (span("serve.admit") if waiting or parked
                      else contextlib.nullcontext()):
                    elig_ok = not (policy == "static" and sched.live_slots())
                    if paged:
                        was_live = bool(sched.live_slots())
                        # parked level-1 wakes first: bitwise restore, no
                        # prefill
                        if elig_ok:
                            for rid in list(parked):
                                if parked[rid].level == 1 and \
                                        try_wake_level1(parked[rid]):
                                    parked.pop(rid)
                        # then level-2 resumes (re-prefill at prompt_len + gen
                        # width) and fresh admissions, one block-pool plan each
                        cands = []
                        if elig_ok:
                            cands = [(sched.requests[rid], parked[rid])
                                     for rid in list(parked)
                                     if parked[rid].level == 2]
                            cands += [(r, None) for r in
                                      admission_order(len(sched.free_slots()))]
                        S_res = S_pad + self.gen
                        plans = {}          # (kind, width) -> [admission]
                        cow_pairs, cow_pins, poison_slots = [], [], []
                        admitted_rids = set()
                        for req, p in cands:
                            free_now = sched.free_slots()
                            if not free_now:
                                break
                            slot = free_now[0]
                            prompt = np.asarray(req.prompt, np.int64)
                            if p is not None and p.generated:
                                prompt = np.concatenate(
                                    [prompt,
                                     np.asarray(p.generated, np.int64)])
                            try:
                                hist_n, cow = pool.admit(
                                    slot, prompt,
                                    pending_all=bool(prefill_chunk))
                            except paging.PoolExhausted:
                                break   # completions will free blocks; wait
                            sched.admit(slot, req, t, len(history),
                                        resume=p is not None,
                                        prefilling=bool(prefill_chunk))
                            set_sampling(slot, req)
                            refresh_row(slot)
                            poisoned = (self.injector is not None and
                                        self.injector.fires("poison_request",
                                                            req.rid))
                            if prefill_chunk:
                                # chunked admission bypasses the plans
                                # machinery: the chunk phase below prefills
                                # positions hist_n.. one chunk per iteration
                                # (a prefix-cache hit skips straight to the
                                # first cold chunk). The full-hit CoW copy
                                # runs NOW — its source blocks already hold
                                # written content.
                                req.status = "prefilling"
                                row_len[slot] = hist_n
                                if cow:
                                    do_cow([cow[:2]])
                                prefill_jobs[slot] = {
                                    "req": req, "prompt": prompt,
                                    "off": hist_n, "hist0": hist_n,
                                    "blocks": [b for b in
                                               pool.slot_blocks[slot]
                                               if b in pool.pending],
                                    "poison": poisoned}
                            else:
                                row_len[slot] = len(prompt)
                                if cow:
                                    # the device copy is deferred until the
                                    # source's content is valid — pin it so
                                    # a later admission in this round cannot
                                    # reclaim + overwrite it first
                                    cow_pairs.append(cow[:2])
                                    cow_pins.append(cow[0])
                                    pool.pin(cow[0])
                                key2 = ("shared" if hist_n else "fresh",
                                        S_pad if p is None else S_res)
                                plans.setdefault(key2, []).append(
                                    (slot, req, prompt, hist_n))
                            if was_live and t > 0:
                                admitted_mid_decode += 1
                            if p is not None:
                                parked.pop(req.rid)
                                wakes += 1
                                pool._log("page_wake", slot, req.rid)
                                self.events.append("wake", t, rid=req.rid,
                                                   slot=slot, level=2)
                            else:
                                admitted_rids.add(req.rid)
                            if poisoned:
                                if not prefill_chunk:
                                    poison_slots.append(slot)
                                self.events.append("inject", t,
                                                   site="poison_request",
                                                   rid=req.rid, slot=slot)
                        waiting = [r for r in waiting
                                   if r.rid not in admitted_rids]
                        if plans:
                            cache["table"] = jnp.asarray(st["table"].copy())
                            # fresh admissions prefill (and REGISTER their
                            # blocks) before shared ones read them —
                            # intra-batch sharing. CoW copies run BETWEEN the
                            # two: after the fresh prefills have written every
                            # source block, before any shared prefill reads
                            # its private copy.
                            order = sorted(plans,
                                           key=lambda k: k[0] != "fresh")
                            cow_done = False
                            for kind, width in order:
                                if kind == "shared" and not cow_done:
                                    do_cow(cow_pairs)
                                    for b in cow_pins:
                                        pool.unpin(b)
                                    cow_done = True
                                items = plans[(kind, width)]
                                prompts = np.zeros((B, width), np.int32)
                                lengths = np.zeros((B,), np.int32)
                                hist_a = np.zeros((B,), np.int32)
                                mask = np.zeros((B,), bool)
                                rids = np.zeros((B,), np.int32)
                                if kind == "shared":
                                    # non-admitted rows: empty tail at
                                    # their own length — no writes, lengths
                                    # preserved
                                    lengths[:] = row_len
                                    hist_a[:] = row_len
                                for slot, req, prompt, hist_n in items:
                                    mask[slot] = True
                                    rids[slot] = _sid(req)
                                    lengths[slot] = len(prompt)
                                    hist_a[slot] = hist_n
                                    tail = (prompt[hist_n:]
                                            if kind == "shared" else prompt)
                                    prompts[slot, :len(tail)] = tail
                                if kind == "fresh":
                                    tok, cache, keys = fns["admit_fresh"](
                                        self.params, jnp.asarray(prompts),
                                        jnp.asarray(np.maximum(lengths, 1)),
                                        jnp.asarray(mask), jnp.asarray(rids),
                                        tok, cache, keys, *samp())
                                else:
                                    tok, cache, keys = fns["admit_shared"](
                                        self.params, jnp.asarray(prompts),
                                        jnp.asarray(lengths),
                                        jnp.asarray(hist_a),
                                        jnp.asarray(mask), jnp.asarray(rids),
                                        tok, cache, keys, *samp())
                                prefill_calls += 1
                            if not cow_done:    # defensive: cow, no shared
                                do_cow(cow_pairs)
                                for b in cow_pins:
                                    pool.unpin(b)
                            # every planned prefill has executed: blocks
                            # registered by this round's shared-tail
                            # admissions now hold real content and become
                            # prefix-matchable again
                            pool.mark_written()
                            if poison_slots:
                                paged_poison(poison_slots)
                    else:
                        free = sched.free_slots()
                        elig = admission_order(len(free)) if elig_ok else []
                        take = min(len(free), len(elig))
                        if take and prefill_chunk:
                            # chunked admission: allocate the slot and open a
                            # prefill job — the chunk phase below pushes the
                            # first chunk THIS iteration, so scheduling is
                            # unchanged
                            was_live = bool(sched.live_slots())
                            admitted_rids = set()
                            for slot, req in zip(free[:take], elig[:take]):
                                sched.admit(slot, req, t, len(history),
                                            prefilling=True)
                                req.status = "prefilling"
                                set_sampling(slot, req)
                                poisoned = (
                                    self.injector is not None and
                                    self.injector.fires("poison_request",
                                                        req.rid))
                                if poisoned:
                                    self.events.append("inject", t,
                                                       site="poison_request",
                                                       rid=req.rid, slot=slot)
                                prefill_jobs[slot] = {
                                    "req": req,
                                    "prompt": np.asarray(req.prompt, np.int64),
                                    "off": 0, "hist0": 0, "blocks": [],
                                    "poison": poisoned}
                                admitted_rids.add(req.rid)
                                if was_live and t > 0:
                                    admitted_mid_decode += 1
                            waiting = [r for r in waiting
                                       if r.rid not in admitted_rids]
                        elif take:
                            was_live = bool(sched.live_slots())
                            prompts = np.zeros((B, S_pad), np.int32)
                            lengths = np.ones((B,), np.int32)
                            mask = np.zeros((B,), bool)
                            rids = np.zeros((B,), np.int32)
                            poison = np.zeros((B,), bool)
                            admitted_rids = set()
                            for slot, req in zip(free[:take], elig[:take]):
                                prompts[slot, :len(req.prompt)] = req.prompt
                                lengths[slot] = len(req.prompt)
                                mask[slot] = True
                                rids[slot] = _sid(req)
                                sched.admit(slot, req, t, len(history))
                                set_sampling(slot, req)
                                admitted_rids.add(req.rid)
                                if was_live and t > 0:
                                    admitted_mid_decode += 1
                                if self.injector is not None and \
                                        self.injector.fires("poison_request",
                                                            req.rid):
                                    poison[slot] = True
                                    self.events.append("inject", t,
                                                       site="poison_request",
                                                       rid=req.rid, slot=slot)
                            waiting = [r for r in waiting
                                       if r.rid not in admitted_rids]
                            tok, cache, keys = fns["admit"](
                                self.params, jnp.asarray(prompts),
                                jnp.asarray(lengths), jnp.asarray(mask),
                                jnp.asarray(rids), tok, cache, keys, *samp())
                            prefill_calls += 1
                            if poison.any():
                                cache = fns["poison"](cache,
                                                      jnp.asarray(poison))
                # -- chunked prefill: every prefilling slot advances ONE chunk
                # (one batched call per iteration; rows on their FINAL chunk
                # sample their first token exactly like a legacy admission, so
                # it is logged as this iteration's emission)
                if prefill_jobs:
                    with span("serve.prefill"):
                        C = prefill_chunk
                        tails = np.zeros((B, C), np.int32)
                        lengths = np.zeros((B,), np.int32)
                        hist_a = np.zeros((B,), np.int32)
                        mask = np.zeros((B,), bool)
                        rids = np.zeros((B,), np.int32)
                        # passenger rows: empty tail at their own EXACT
                        # length — no writes, length preserved (the paged
                        # shared-tail admission convention)
                        mirror = row_len if paged else dense_len
                        lengths[:] = mirror
                        hist_a[:] = mirror
                        finals = []
                        for slot, job in prefill_jobs.items():
                            prompt, off = job["prompt"], job["off"]
                            end = min(off + C, len(prompt))
                            tails[slot, :end - off] = prompt[off:end]
                            lengths[slot] = end
                            hist_a[slot] = off
                            last = end >= len(prompt)
                            mask[slot] = last
                            rids[slot] = _sid(job["req"])
                            job["off"] = end
                            if last:
                                finals.append((slot, job))
                        chunk_fn = fns["admit_shared"] if paged \
                            else fns["admit_chunk"]
                        if paged:
                            cache["table"] = jnp.asarray(st["table"].copy())
                        tok, cache, keys = chunk_fn(
                            self.params, jnp.asarray(tails),
                            jnp.asarray(lengths),
                            jnp.asarray(hist_a), jnp.asarray(mask),
                            jnp.asarray(rids), tok, cache, keys, *samp())
                        prefill_calls += 1
                        for slot, job in prefill_jobs.items():
                            mirror[slot] = job["off"]
                        pmask = np.zeros((B,), bool)
                        for slot, job in finals:
                            prefill_jobs.pop(slot)
                            req = job["req"]
                            sched.prefill_done(slot, t, len(history))
                            req.status = "queued"
                            if paged:
                                pool.mark_written(job["blocks"])
                            pmask[slot] = job["poison"]
                            self.events.append(
                                "prefill_done", t, rid=req.rid, slot=slot,
                                hist=job["hist0"],
                                chunks=-(-(len(job["prompt"]) -
                                           job["hist0"]) // C))
                        if pmask.any():
                            if paged:
                                paged_poison([s for s, j in finals
                                              if j["poison"]])
                            else:
                                cache = fns["poison"](cache,
                                                      jnp.asarray(pmask))
                # -- paged: make every live row's next write position resident
                # (BEFORE the emission is logged: a preempted row's pending
                # token stays pending, so its wake re-injects it exactly once)
                if paged and sched.live_slots():
                    with span("serve.kv_grow"):
                        cow_pairs, dirty = [], False
                        for slot in list(sched.live_slots()):
                            rid = sched.owner[slot]
                            if rid is None or slot in sched.prefilling:
                                continue  # parked victim, or mid-prefill
                            # the block is allocated even for a request
                            # completing this step (released again at
                            # completion): the decode READS the position it
                            # just wrote, so the write must land in a real
                            # exclusive block — writes redirected to the
                            # write-off path are dropped, not read back
                            while sched.owner[slot] is not None:
                                try:
                                    new, cow = pool.prepare_write(
                                        slot, int(row_len[slot]))
                                except paging.PoolExhausted:
                                    # LIFO victim — maybe self
                                    park(lifo_victim(),
                                         f"pool exhausted growing slot {slot}")
                                    continue
                                for lb, phys in new:
                                    st["table"][slot, lb] = phys
                                    dirty = True
                                if cow:
                                    cow_pairs.append(cow[:2])
                                    st["table"][slot, cow[2]] = cow[1]
                                    dirty = True
                                break
                        do_cow(cow_pairs)
                        if dirty:
                            cache["table"] = jnp.asarray(st["table"].copy())
                live = sched.live_slots()
                if not live:
                    if not pending and not waiting and not parked:
                        break                # everything terminal: done
                    if clock == "wall" and pending:
                        # real-time idle: sleep toward the next arrival instead
                        # of spinning the iteration counter
                        gap = arr_of(pending[0]) - clock_now()
                        if gap > 0:
                            with span("serve.wait"):
                                time.sleep(min(gap, 0.05))
                    t += 1               # idle tick: clock runs to the next
                    continue             # arrival without touching devices
                # -- fused host sync: tokens (eos / streaming) + row health ---
                # (ONE [B]-sized transfer per iteration — never one per
                # concern)
                host_tok = None
                if need_sync:
                    with span("serve.sync"):
                        if guard:
                            synced = np.asarray(fns["sync"](tok, cache))
                            host_tok = synced[0]
                            health = synced[1].astype(bool)
                        else:
                            host_tok = np.asarray(tok)
                        host_syncs += 1
                        if guard:
                            # quarantine at the emission point: a row
                            # poisoned at admission is evicted BEFORE its
                            # first token is logged
                            quarantine(health, time.perf_counter())
                            live = sched.live_slots()
                            if not live:
                                t += 1
                                continue
                emitting = [(s, sched.owner[s]) for s in live
                            if s not in sched.prefilling]
                if emitting:
                    with span("serve.emit"):
                        # -- log this iteration's emission for every
                        # emitting slot
                        history.append(tok)
                        emission_iters += 1
                        owners = np.full((B,), -1, np.int64)
                        for s, rid in emitting:
                            owners[s] = rid
                            first_emit.setdefault(rid, cnow)
                        owners_log.append(owners)
                        eos_hit = None
                        if eos_id is not None:
                            eos_hit = [bool(host_tok[s] == eos_id)
                                       for s in range(B)]
                        done_now = sched.log_emissions(t, time.perf_counter(),
                                                       eos_hit)
                        if host_tok is not None and stream_hooks:
                            # streaming observes the host copy only — nothing
                            # feeds back into the jitted fns
                            for s, rid in emitting:
                                req = sched.requests[rid]
                                tkn = int(host_tok[s])
                                if req.on_token is not None:
                                    req.on_token(rid, tkn, t, cnow)
                                if self._stream_cb is not None:
                                    self._stream_cb(rid, tkn, t, cnow)
                        for s in done_now:   # completion frees the blocks;
                            release_slot_resources(s, upload=False)
                        if paged and done_now:   # ONE table upload a step,
                            cache["table"] = jnp.asarray(st["table"].copy())
                # -- one ragged decode step for the whole slot batch ----------
                # (only when an emitting row still needs it: a freshly admitted
                # request's first token comes from the prefill, not step; a
                # mid-prefill row neither emits nor decodes)
                live_now = [s for s in sched.live_slots()
                            if s not in sched.prefilling]
                if live_now:
                    with span("serve.decode"):
                        if wd is not None:
                            wd.arm(t)
                        if prefill_chunk:
                            # mid-prefill rows are INACTIVE: their cache
                            # writes are dropped and their lengths stay frozen
                            act = np.ones((B,), bool)
                            for s in prefill_jobs:
                                act[s] = False
                            tok, cache, keys = fns["step_active"](
                                self.params, tok, cache, keys, *samp(),
                                jnp.asarray(act))
                            if not paged:
                                dense_len[act] += 1
                        else:
                            tok, cache, keys = fns["step"](
                                self.params, tok, cache, keys, *samp())
                        decode_steps += 1
                        if wd is not None:
                            # the watchdog's verdict needs the step's results
                            # on the host — opting in trades async dispatch
                            # for a truthful per-step latency reading
                            jax.block_until_ready(tok)
                            over = wd.expired()
                            if over is not None:
                                self.events.append("slow_step", t,
                                                   elapsed_s=round(over, 6),
                                                   timeout_s=wd.timeout_s)
                            wd.disarm()
                        if paged:
                            for s in live_now:
                                row_len[s] += 1
                t += 1
        with span("serve.finish"):
            jax.block_until_ready(tok)
            wall = time.perf_counter() - t_start

            if truncated:
                now = time.perf_counter()
                for slot in sched.live_slots():
                    rid = sched.evict(slot, t, now, "timeout")
                    sched.requests[rid].status = "timeout"
                    sched.requests[rid].error = \
                        f"max_steps={max_steps} exhausted"
                    release_slot_resources(slot)
                    self.events.append("timeout", t, rid=rid,
                                       where="max_steps")
                for rid in list(parked):
                    parked.pop(rid)          # payload dropped with it
                    r = sched.requests[rid]
                    r.status = "timeout"
                    r.error = f"max_steps={max_steps} exhausted"
                    sched.close(rid, t, now, "timeout")
                    self.events.append("timeout", t, rid=rid,
                                       where="max_steps")
                for r in waiting + pending:
                    r.status = "timeout"
                    r.error = f"max_steps={max_steps} exhausted"
                    r.tokens = np.zeros((0,), np.int32)
                    self.events.append("timeout", t, rid=r.rid,
                                       where="max_steps")
                self._log(f"serve[{policy}]: max_steps={max_steps} "
                          f"exhausted — returning partial results")

            hist = (np.asarray(jnp.stack(history))   # ONE transfer
                    if history else np.zeros((0, B), np.int32))
        for rid, req in sched.requests.items():
            # a request's stream may span several (history, slot) segments
            # when the paged pool preempted and resumed it
            parts = [hist[h:h + c, s]
                     for h, s, c in sched.token_segments(rid)]
            req.tokens = (np.concatenate(parts).astype(np.int32) if parts
                          else np.zeros((0,), np.int32))
            if req.status == "queued":   # untouched by evict/timeout paths
                req.status = "ok"

        done = [r for r in requests
                if r.rid in sched.complete_time and r.rid in arrival_wall]
        lat_s = np.array([sched.complete_time[r.rid] - arrival_wall[r.rid]
                          for r in done]) if done else np.zeros((0,))
        lat_steps = np.array([sched.complete_step[r.rid] - r.arrival_step
                              for r in done]) if done else np.zeros((0,))
        pct = lambda a, q: round(float(np.percentile(a, q)), 4) \
            if len(a) else 0.0
        status_counts: Dict[str, int] = {}
        for r in requests:
            status_counts[r.status] = status_counts.get(r.status, 0) + 1
        total = int(sum(sched.gen_done.values()))
        metrics = {
            "policy": policy, "n_requests": len(requests),
            "n_slots": B, "total_generated": total,
            "wall_s": round(wall, 4),
            "decode_tok_s": round(total / max(wall, 1e-9), 2),
            "decode_steps": decode_steps, "prefill_calls": prefill_calls,
            "admitted_mid_decode": admitted_mid_decode,
            "status_counts": status_counts,
            "truncated": truncated,
            "latency_s": {"p50": pct(lat_s, 50), "p99": pct(lat_s, 99),
                          "mean": round(float(lat_s.mean()), 4)
                          if len(lat_s) else 0.0},
            "latency_steps": {"p50": pct(lat_steps, 50),
                              "p99": pct(lat_steps, 99)},
        }
        # wall-clock serving metrics: TTFT is first-token latency in clock
        # units (steps on the step clock, seconds on wall/virtual);
        # goodput is the fraction of requests that finished "ok" — i.e.
        # inside their deadline, since expiry flips status to "timeout"
        ttft_vals = np.array([first_emit[r.rid] - arr_of(r)
                              for r in requests if r.rid in first_emit])
        metrics.update({
            "clock": clock,
            "admission": adm.name,
            "prefill_chunk": prefill_chunk,
            "host_syncs": host_syncs,
            "emission_iters": emission_iters,
            "goodput": round(status_counts.get("ok", 0) / len(requests), 4),
            "ttft": {"p50": pct(ttft_vals, 50), "p99": pct(ttft_vals, 99)},
        })
        if paged:
            st["cache"] = cache          # persist: the prefix cache stays
            lookup = pool.prefix_lookup_tokens
            metrics["paging"] = {
                "pool_blocks": pool.num_blocks,
                "block_size": bs,
                "blocks_in_use_peak": pool.in_use_peak,
                "prefix_hit_rate": round(
                    pool.prefix_hit_tokens / lookup, 4) if lookup else 0.0,
                "prefill_tokens_requested": lookup,
                "marginal_prefill_tokens": lookup - pool.prefix_hit_tokens,
                "preemptions": preemptions,
                "offloads": offloads,
                "wakes": wakes,
                "cow_copies": pool.cow_copies,
                "sleep_level": self.sleep_level,
                "prefix_cache": self.prefix_cache,
            }
            pg = metrics["paging"]
            self._log(
                f"serve[paged]: {pg['pool_blocks']} blocks x "
                f"{pg['block_size']} tok, peak {pg['blocks_in_use_peak']} "
                f"in use, prefix hit rate {pg['prefix_hit_rate']}, "
                f"{pg['marginal_prefill_tokens']}/"
                f"{pg['prefill_tokens_requested']} prefill tokens computed, "
                f"{preemptions} preemptions ({offloads} offloads, "
                f"{wakes} wakes)")
        self._log(
            f"serve[{policy}]: {len(requests)} requests over {B} slots in "
            f"{wall:.2f}s — {metrics['decode_tok_s']} tok/s, "
            f"{decode_steps} decode steps, {prefill_calls} admission "
            f"prefills ({admitted_mid_decode} requests admitted mid-decode), "
            f"status {status_counts}, "
            f"latency p50/p99 {metrics['latency_s']['p50']}/"
            f"{metrics['latency_s']['p99']}s")
        return {"requests": sorted(requests, key=lambda r: r.rid),
                "events": sched.events, "owners_log": owners_log,
                "scheduler": sched, "metrics": metrics,
                "engine_events": self.events}
