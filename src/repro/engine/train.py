"""TrainEngine: build -> jitted CDP step -> log/checkpoint/resume loop.

The one training code path: ``launch/train.py`` is an argparse shim over
this class, the examples drive it directly, and tests exercise
checkpoint/resume equality through it.

    spec = RunSpec(arch="stablelm-1.6b", reduced=True, host_devices=4)
    engine = TrainEngine(spec, plan="zero_cdp", steps=100, ckpt_dir="ckpts/")
    engine.run()                       # resumes automatically from ckpt_dir

The parallelism strategy is a ``repro.parallel`` plan (``plan=`` here or on
the RunSpec): ``dp`` | ``cdp_v1`` | ``cdp_v2`` | ``cdp_random`` |
``zero1_ring`` | ``zero_cdp``. ``rule=`` survives as an alias for the plan
of the same name.

Determinism contract: with a fixed RunSpec.seed the data stream is a pure
function of the step index — on restore the engine fast-forwards the host
iterator to the restored step, so an interrupted+resumed run produces
exactly the same state as an uninterrupted one (tested in
tests/test_engine.py).

Resilience (``resilience=`` / ``guard=`` / ``keep_last=``; see
``engine.resilience``): with the health guard on, every step's loss is
checked for finiteness and EMA spikes — a bad step's update is SKIPPED
(the pre-step params are reused, which is legal under CDP's
uniform-staleness rules: a dropped micro-batch update is just another
bounded delay) and ``guard_max_bad`` consecutive bad steps roll the engine
back to the newest intact checkpoint, replaying the data stream from
there. Loader-worker crashes are retried by rebuilding the stream at the
current step (the stream is a pure function of the step index, so the
retried batch is bit-identical). Every skip / rollback / retry / injected
fault lands in the structured ``engine.events`` log. The guard needs the
pre-step state alive, so it forces ``donate=False``.

Elastic membership (``elastic=True``; see ``engine.elastic``): the run
survives the LOSS OF A DATA RANK. ``snapshot_every`` arms buddy-replicated
host-RAM snapshots (each rank's ZeRO-CDP chunk mirrored to its ring
predecessor); on a ``rank_down`` fault — or a step blowing past
``watchdog_timeout`` seconds, which on a ring is a hung collective — the
engine restores the newest snapshot (disk checkpoint as fallback), drops
the dead device, re-forms the mesh at N-1, re-cuts the stage chunks via
``build_stage_layout(cfg, n-1)``, re-jits, and resumes with the data
stream fast-forwarded: at most ``snapshot_every`` steps lost, and the
post-recovery trajectory is bit-identical to an uninterrupted N-1 run
from the snapshot step. ``rejoin_after`` scales back up (N-1 -> N re-cut)
at a step boundary once the failed rank returns.
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional

from repro.engine import resilience as rsl
from repro.engine.spec import RunSpec

PyTree = Any


class TrainEngine:
    def __init__(self, spec: RunSpec, *,
                 plan=None,                    # ParallelPlan | name | None
                 rule: Optional[str] = None,   # alias: plan of the same name
                 steps: int = 100,
                 batch: int = 8,
                 seq: int = 128,
                 lr: float = 0.05,
                 momentum: float = 0.9,
                 weight_decay: float = 1e-4,
                 lr_schedule: Optional[Callable] = None,
                 optimizer=None,
                 trainer=None,                 # full TrainerConfig override
                 loss_fn: Optional[Callable] = None,
                 ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 50,
                 keep_last: Optional[int] = None,
                 log_every: int = 10,
                 data_tokens: int = 200_000,
                 donate: bool = True,
                 resilience=None,              # FaultInjector | spec str | None
                 guard: Optional[bool] = None,  # None = on iff resilience
                 guard_spike_factor: float = 10.0,
                 guard_max_bad: int = 3,
                 loader_retries: int = 2,
                 elastic: bool = False,
                 snapshot_every: int = 0,       # buddy snapshots (0 = off)
                 watchdog_timeout: float = 0.0,  # step deadline s (0 = off)
                 rejoin_after: int = 0,  # steps after recovery to scale up
                 verbose: bool = True):
        spec.ensure_host_devices()
        self.spec = spec
        if plan is not None and rule is not None:
            raise ValueError("pass plan= or rule= (alias), not both")
        # precedence: trainer= override's plan > explicit plan > rule alias
        # > spec.plan > cdp_v2; a bad name fails fast here, before any jax
        # work (repro.parallel is jax-free, like RunSpec resolution)
        if trainer is not None:
            if plan is not None or rule is not None:
                raise ValueError(
                    "a trainer= override carries its own plan; do not also "
                    "pass plan=/rule=")
            self.plan = trainer.resolved_plan()
        else:
            from repro.parallel import resolve_plan
            self.plan = resolve_plan(
                plan if plan is not None else
                (rule if rule is not None else spec.plan))
        self.rule = self.plan.name            # back-compat: engine.rule
        self.steps = steps
        self.batch = batch
        self.seq = seq
        self.lr = lr
        self.momentum = momentum
        self.weight_decay = weight_decay
        self.lr_schedule = lr_schedule
        self.optimizer = optimizer
        self.trainer_override = trainer
        self.custom_loss_fn = loss_fn
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep_last = keep_last
        self.log_every = log_every
        self.data_tokens = data_tokens
        self.verbose = verbose

        # -- resilience layer ------------------------------------------------
        self.injector = rsl.FaultInjector.from_spec(resilience,
                                                    seed=spec.seed)
        if guard is None:
            guard = self.injector is not None
        self.guard = rsl.HealthGuard(spike_factor=guard_spike_factor) \
            if guard else None
        self.guard_max_bad = guard_max_bad
        self.loader_retries = loader_retries
        self.events = rsl.EventLog()
        self._bad_streak = 0

        # -- elastic membership ----------------------------------------------
        self.elastic = bool(elastic)
        self.snapshot_every = int(snapshot_every)
        self.rejoin_after = int(rejoin_after)
        self.watchdog = rsl.StepWatchdog(watchdog_timeout) \
            if watchdog_timeout else None
        self.recoveries: List[Dict[str, Any]] = []
        self._snapshots = None        # engine.elastic.BuddySnapshotStore
        self._snapshot_s: List[float] = []
        self._rejoin_at: Optional[int] = None
        self._n_data = 0              # current data-axis size (set by build)
        self._fresh_program = True    # first step after a (re)jit compiles;
                                      # the watchdog must not count that
        if self.guard is not None:
            # skipping a bad update reuses the PRE-step state, so its
            # buffers must survive the step: donation is incompatible
            if trainer is not None and trainer.donate:
                raise ValueError(
                    "the health guard needs the pre-step state alive; pass "
                    "a TrainerConfig with donate=False (or guard=False)")
            donate = False
        self.donate = donate

        self.cfg = spec.resolve_config()
        self.mesh = None
        self.state = None
        self.start_step = 0
        self.history: List[Dict[str, float]] = []
        self._built = False
        self._loader = None
        self._extras = None
        self._hlo_text = None
        self._step_exec = None        # AOT executable (set by hlo_text)
        self._stream_step = 0         # step index of the next host batch
        self._ext_steps = {}          # batch-structure -> jitted ext step

    # -- plumbing ----------------------------------------------------------

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(msg, flush=True)

    def _make_trainer_config(self):
        from repro.core.trainer import TrainerConfig
        from repro.optim import cosine_warmup
        if self.trainer_override is not None:
            return self.trainer_override
        sched = self.lr_schedule or cosine_warmup(
            self.lr, max(1, self.steps // 10), self.steps)
        return TrainerConfig(
            plan=self.plan,
            pod_axis="pod" if self.spec.mesh_pod else None,
            lr_schedule=sched, donate=self.donate)

    def _proto_extras(self):
        """Family side-inputs (patches/frames protos) — constant across
        steps, so allocated once, not per batch in the loader hot path."""
        if self._extras is None:
            from repro.data.synthetic import synthetic_batch
            proto = synthetic_batch(self.cfg, type("S", (), {
                "global_batch": self.batch, "seq_len": self.seq})())
            self._extras = {k: proto[k] for k in ("patches", "frames")
                            if k in proto}
        return self._extras

    def _to_batch(self, host_batch):
        import jax.numpy as jnp
        b = {"tokens": jnp.asarray(host_batch["tokens"]),
             "targets": jnp.asarray(host_batch["targets"])}
        b.update(self._proto_extras())
        return b

    # -- lifecycle ---------------------------------------------------------

    def build(self) -> "TrainEngine":
        """Materialise params/optimizer/mesh, jit the step, restore the
        newest INTACT checkpoint when ckpt_dir has one (broken files are
        skipped with a ``ckpt_fallback`` event). Idempotent."""
        if self._built:
            return self
        import jax
        import numpy as np
        from repro import checkpoint as ckpt
        from repro.core.trainer import init_state, jit_train_step
        from repro.data import lm_batch_iterator, make_lm_data
        from repro.models import init_params
        from repro.optim import sgd_momentum

        self.mesh = self.spec.build_mesh()
        self._log(f"mesh: {dict(self.mesh.shape)}  arch: {self.cfg.name}  "
                  f"plan: {self.plan.name} (rule={self.plan.rule}, "
                  f"sync={self.plan.sync}, placement={self.plan.placement})")

        params = init_params(self.cfg, jax.random.PRNGKey(self.spec.seed))
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        self._log(f"params: {n_params/1e6:.2f}M")

        self.opt = self.optimizer or sgd_momentum(self.momentum,
                                                  self.weight_decay)
        self.trainer = self._make_trainer_config()
        self._n_data = self.mesh.shape[self.trainer.data_axis]
        self.state = init_state(self.cfg, self.trainer, params, self.opt,
                                mesh=self.mesh)

        tokens = make_lm_data(self.cfg.vocab_size, self.data_tokens,
                              seed=self.spec.seed)
        self._host_it = lm_batch_iterator(tokens, self.batch, self.seq,
                                          seed=self.spec.seed)
        batch0 = self._to_batch(next(self._host_it))
        self._batch0 = batch0
        self.step_fn, self.state_sh, self.batch_sh = jit_train_step(
            self.cfg, self.trainer, self.mesh, self.opt, self.state, batch0,
            self.custom_loss_fn)

        self.start_step = 0
        if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
            try:
                self.state, self.start_step = ckpt.restore(
                    self.ckpt_dir, self.state,
                    on_fallback=lambda s, r: self.events.append(
                        "ckpt_fallback", s, reason=r))
            except FileNotFoundError:
                # every on-disk step is broken: start fresh rather than die
                self.events.append("ckpt_unusable", 0,
                                   dir=self.ckpt_dir)
                self._log(f"no intact checkpoint in {self.ckpt_dir}; "
                          f"starting from step 0")
            else:
                # the synthetic stream is a pure function of the step
                # index: skip what the interrupted run already consumed so
                # resumed == uninterrupted
                for _ in range(self.start_step):
                    next(self._host_it)
                self._log(f"restored step {self.start_step}")
        self._stream_step = self.start_step
        self._fresh_program = True
        self._built = True
        return self

    # -- data stream (resilient) -------------------------------------------

    def _rebuild_stream(self, step: int) -> None:
        """Fresh host iterator fast-forwarded so the next batch is step
        ``step``'s — bit-identical to the original stream (pure function
        of the step index): the recovery path for loader crashes and
        checkpoint rollback."""
        from repro.data import lm_batch_iterator, make_lm_data
        tokens = make_lm_data(self.cfg.vocab_size, self.data_tokens,
                              seed=self.spec.seed)
        it = lm_batch_iterator(tokens, self.batch, self.seq,
                               seed=self.spec.seed)
        next(it)                          # the build()-time trace batch
        for _ in range(step):
            next(it)
        self._host_it = it
        self._stream_step = step

    def _feed(self):
        """Host-batch generator for the loader worker; the loader-site
        fault hook lives here so an injected crash exercises the REAL
        worker-thread error path (exception raised on the prefetch thread,
        surfaced in ``__next__``)."""
        while True:
            try:
                b = next(self._host_it)
            except StopIteration:
                return
            s = self._stream_step
            self._stream_step = s + 1
            if self.injector is not None and self.injector.fires("loader", s):
                raise RuntimeError(
                    f"injected loader-worker fault at step {s}")
            yield self._to_batch(b)

    def _get_loader(self):
        """ONE persistent loader per engine: partial ``run()`` calls share
        it, so prefetched-but-untrained batches are consumed by the next
        call instead of silently dropped (the determinism contract holds
        for in-process continuation, not just checkpoint resume)."""
        from repro.data import ShardedLoader
        if self._loader is None:
            self._loader = ShardedLoader(self._feed(), self.batch_sh)
        return self._loader

    def _next_batch(self, step: int):
        """One batch for ``step``, surviving loader-worker crashes: a
        crashed worker's exception (re-raised by ``ShardedLoader.__next__``
        instead of hanging) is logged, the stream is rebuilt exactly at
        ``step``, and the batch is retried — up to ``loader_retries``
        rebuilds before giving up."""
        for attempt in range(self.loader_retries + 1):
            loader = self._get_loader()
            try:
                return next(loader)
            except StopIteration:
                raise
            except Exception as e:
                self.events.append("loader_error", step, error=repr(e),
                                   attempt=attempt)
                self._log(f"step {step}: loader worker died ({e!r}); "
                          f"rebuilding the stream (attempt {attempt + 1})")
                self.close()
                self._rebuild_stream(step)
        raise RuntimeError(
            f"loader failed {self.loader_retries + 1} times at step {step}")

    # -- checkpoint + rollback ---------------------------------------------

    def _save_checkpoint(self, step: int) -> None:
        from repro import checkpoint as ckpt
        path = ckpt.save(self.ckpt_dir, step, self.state,
                         keep_last=self.keep_last, injector=self.injector)
        self.events.append("ckpt_save", step)
        if self.injector is not None and \
                self.injector.fires("ckpt_truncate", step):
            # disk corruption / kill -9 straight after the commit: the
            # manifest checksum no longer matches, so restore() must skip
            # this step
            size = os.path.getsize(path)
            with open(path, "r+b") as fh:
                fh.truncate(max(1, size // 2))
            self.events.append("inject", step, site="ckpt_truncate")

    def _rollback(self, step: int) -> int:
        """Too many consecutive bad steps: restore the newest intact
        checkpoint, rewind the data stream to it, reset the guard.
        Returns the restored step (the new loop position)."""
        import jax
        from repro import checkpoint as ckpt
        restored = None
        if self.ckpt_dir:
            try:
                restored = ckpt.restore(
                    self.ckpt_dir, self.state,
                    on_fallback=lambda s, r: self.events.append(
                        "ckpt_fallback", s, reason=r))
            except FileNotFoundError:
                restored = None
        if restored is None:
            self.events.append("rollback_failed", step,
                               streak=self._bad_streak)
            raise RuntimeError(
                f"{self._bad_streak} consecutive bad steps at step {step} "
                f"and no intact checkpoint to roll back to "
                f"(ckpt_dir={self.ckpt_dir!r})")
        state, rstep = restored
        self.state = jax.device_put(state, self.state_sh)
        self.events.append("rollback", step, to_step=rstep,
                           streak=self._bad_streak)
        self._log(f"step {step}: {self._bad_streak} consecutive bad steps "
                  f"— rolling back to checkpoint step {rstep}")
        self.close()
        self._rebuild_stream(rstep)
        self.guard.reset()
        self._bad_streak = 0
        return rstep

    def _bump_step(self, state):
        """Advance ONLY the step counter (a skipped update keeps params and
        optimizer state): the loop position, LR schedule and CDP freshness
        stay in lockstep with the uninterrupted trajectory."""
        import jax
        import numpy as np
        new = dict(state)
        new["step"] = jax.device_put(np.int32(int(state["step"]) + 1),
                                     self.state_sh["step"])
        return new

    # -- elastic membership: snapshot / shrink / rejoin ----------------------

    def _state_template(self):
        """Shape/dtype skeleton of the CURRENT state layout — what the
        snapshot/checkpoint restore paths key on. Values are never read,
        so this stays valid even when the live buffers were donated."""
        import jax
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)

    def _host_state(self):
        import jax
        import numpy as np
        return jax.tree.map(lambda x: np.asarray(x), self.state)

    def _stage_sharded(self) -> bool:
        from repro.parallel import PLACE_STAGE_SHARDED
        return self.plan.placement == PLACE_STAGE_SHARDED

    def _take_snapshot(self, step: int) -> None:
        """Park a consistent snapshot of the committed state in the buddy
        store (``step`` = the resume point: the next step to run)."""
        from repro.engine import elastic as el
        t0 = time.monotonic()
        if self._snapshots is None or self._snapshots.n != self._n_data:
            self._snapshots = el.BuddySnapshotStore(
                self._n_data, chunked=self._stage_sharded())
        self._snapshots.take(step, self._host_state())
        dur = time.monotonic() - t0
        self._snapshot_s.append(dur)
        self.events.append("snapshot", step, dur_s=dur, n=self._n_data,
                           bytes=self._snapshots.nbytes)

    def _restore_point_for(self, step: int, dead: int):
        """(host_state, restored_step, source) for a rank-down recovery:
        the buddy snapshot when it survives the death, else the newest
        intact disk checkpoint. The state comes back at the OLD (pre-
        shrink) layout — the caller re-cuts it."""
        from repro import checkpoint as ckpt
        from repro.engine import elastic as el
        template = self._state_template()
        if self._snapshots is not None:
            self._snapshots.fail(dead)
            try:
                state, rstep = self._snapshots.assemble(template)
                return state, rstep, "snapshot"
            except el.SnapshotUnusable as e:
                self.events.append("snapshot_unusable", step, reason=str(e))
                self._log(f"step {step}: buddy snapshot unusable ({e}); "
                          f"falling back to disk")
        if self.ckpt_dir:
            try:
                state, rstep = ckpt.restore(
                    self.ckpt_dir, template,
                    on_fallback=lambda s, r: self.events.append(
                        "ckpt_fallback", s, reason=r))
                return state, rstep, "checkpoint"
            except FileNotFoundError:
                pass
        raise RuntimeError(
            f"data rank {dead} died at step {step} with no usable buddy "
            f"snapshot and no intact checkpoint "
            f"(snapshot_every={self.snapshot_every}, "
            f"ckpt_dir={self.ckpt_dir!r})")

    def _reprogram(self, host_state, stream_step: int) -> None:
        """Re-jit the step for the CURRENT mesh, land ``host_state`` on it,
        and invalidate everything compiled or prefetched for the old one
        (AOT executable, external-batch jits, the loader's shardings)."""
        import jax
        from repro.core.trainer import jit_train_step
        self.step_fn, self.state_sh, self.batch_sh = jit_train_step(
            self.cfg, self.trainer, self.mesh, self.opt, host_state,
            self._batch0, self.custom_loss_fn)
        self.state = jax.device_put(host_state, self.state_sh)
        self._hlo_text = None
        self._step_exec = None
        self._ext_steps = {}
        self._fresh_program = True
        self.close()
        self._rebuild_stream(stream_step)
        self._snapshots = None        # old-layout shards cannot restore the
                                      # resized ring; next take() re-creates

    def _recover_rank_down(self, step: int, dead: int, cause: str) -> int:
        """Rank ``dead`` is gone: re-form the ring on the N-1 survivors
        from the newest consistent snapshot (disk as fallback) and resume.
        Returns the restored step (the new loop position)."""
        n_old = self._n_data
        self.events.append("rank_down", step, rank=dead, cause=cause,
                           n=n_old)
        self._log(f"step {step}: data rank {dead} is down ({cause})")
        if not self.elastic:
            raise RuntimeError(
                f"data rank {dead} went down at step {step} and elastic "
                "membership is off (pass elastic=True / --elastic)")
        if not 0 <= dead < n_old:
            raise ValueError(
                f"rank_down rank {dead} outside the data axis (size {n_old})")
        from repro.engine.spec import shrink_mesh
        n_new = n_old - 1
        t0 = time.monotonic()
        self.plan.validate_resize(n_old, n_new)
        if self.batch % n_new:
            raise ValueError(
                f"global batch {self.batch} does not divide over the "
                f"{n_new} survivor(s); cannot re-form the ring")
        # pick the restore point BEFORE touching the mesh: the snapshot /
        # checkpoint is at the old layout and restores via its template
        host_state, rstep, source = self._restore_point_for(step, dead)
        self.mesh = shrink_mesh(self.mesh, dead, self.trainer.data_axis)
        if self._stage_sharded():
            from repro.parallel import zero_cdp as zcdp
            host_state = zcdp.recut_stage_state(self.cfg, host_state,
                                                n_old, n_new)
        self._n_data = n_new
        self._reprogram(host_state, rstep)
        if self.guard is not None:
            self.guard.reset()
        self._bad_streak = 0
        if self.watchdog is not None:
            self.watchdog.disarm()
        dur = time.monotonic() - t0
        self.recoveries.append({
            "failed_at": step, "step": rstep, "dead": dead, "cause": cause,
            "n": n_new, "source": source, "steps_lost": step - rstep,
            "duration_s": dur, "state": host_state})
        self.events.append("recover", rstep, failed_at=step, n=n_new,
                           source=source, steps_lost=step - rstep,
                           dur_s=dur)
        self._log(f"re-formed the ring on {n_new} rank(s) from {source} "
                  f"step {rstep} ({step - rstep} step(s) lost, "
                  f"{dur:.2f}s)")
        if self.rejoin_after:
            self._rejoin_at = rstep + self.rejoin_after
        return rstep

    def rejoin(self, step: int) -> None:
        """Scale back up at a step boundary: the failed rank returned, the
        mesh re-forms at the spec's full size and the state is re-cut
        N-1 -> N. No rewind — a step boundary is already a consistent cut
        (the rejoining rank receives its chunk instead of contributing
        one)."""
        n_old, n_new = self._n_data, self.spec.mesh_data
        if n_new <= n_old:
            raise RuntimeError(
                f"rejoin at step {step}: already at {n_old} rank(s)")
        t0 = time.monotonic()
        self.plan.validate_resize(n_old, n_new)
        if self.batch % n_new:
            raise ValueError(
                f"global batch {self.batch} does not divide over "
                f"{n_new} ranks; cannot rejoin")
        host_state = self._host_state()
        self.mesh = self.spec.build_mesh()
        if self._stage_sharded():
            from repro.parallel import zero_cdp as zcdp
            host_state = zcdp.recut_stage_state(self.cfg, host_state,
                                                n_old, n_new)
        self._n_data = n_new
        self._reprogram(host_state, step)
        self._rejoin_at = None
        dur = time.monotonic() - t0
        self.events.append("rejoin", step, n=n_new, dur_s=dur)
        self._log(f"step {step}: failed rank rejoined — ring re-formed at "
                  f"{n_new} ranks ({dur:.2f}s)")

    # -- external batches (the RL rollout path) ------------------------------

    def step_external(self, batch) -> Dict[str, float]:
        """Run ONE jitted train step on an externally built batch instead
        of the LM loader stream — the rollout loop's policy-gradient path.

        The batch may carry leaves the LM stream does not (``mask``,
        ``adv``), so the step is jitted once per batch STRUCTURE (sorted
        keys + shapes + dtypes) through the same ``jit_train_step`` the
        loader path uses — same plan, same donation, same shardings; pass
        a custom ``loss_fn=`` at construction to consume the extra leaves
        (it must return ``(loss, metrics)`` with a ``"loss"`` entry).
        Advances ``self.state`` and returns the metrics as host floats."""
        import jax.numpy as jnp
        from repro.core.trainer import jit_train_step
        self.build()
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        sig = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in batch.items()))
        step_fn = self._ext_steps.get(sig)
        if step_fn is None:
            step_fn, _, _ = jit_train_step(
                self.cfg, self.trainer, self.mesh, self.opt, self.state,
                batch, self.custom_loss_fn)
            self._ext_steps[sig] = step_fn
        self.state, metrics = step_fn(self.state, batch)
        return {k: float(v) for k, v in metrics.items()}

    # -- compiled-step access ----------------------------------------------

    def compile(self):
        """The AOT-compiled train step (builds if needed): its
        ``memory_analysis()`` says what the step holds on the device. The
        executable is kept and ``run()`` steps with it — call this BEFORE
        run() and the whole engine compiles exactly once; after run() it
        costs one extra compile (the jit cache is not shared), cached for
        repeat calls."""
        if self._step_exec is None:
            import jax
            self.build()
            self._step_exec = self.step_fn.lower(self.state,
                                                 self._batch0).compile()
            # unlike jit dispatch, the AOT executable does not auto-place
            # its inputs — commit the state to its shardings once
            self.state = jax.device_put(self.state, self.state_sh)
        return self._step_exec

    def hlo_text(self) -> str:
        """Optimized HLO of the compiled train step (:meth:`compile`) —
        feed to ``launch.roofline.parse_collectives`` to read the plan's
        communication signature (all-reduce burst vs collective-permute
        ring vs streamed stages) off the real program."""
        if self._hlo_text is None:
            self._hlo_text = self.compile().as_text()
        return self._hlo_text

    def close(self) -> None:
        if self._loader is not None:
            self._loader.close()
            self._loader = None

    # -- the loop ----------------------------------------------------------

    def run(self, steps: Optional[int] = None) -> PyTree:
        """Train to ``steps`` (default: the configured total), checkpointing
        and logging on the way. Returns the final state. Stopping early
        (``steps < self.steps``) keeps the loader alive for continuation;
        reaching the configured total closes it."""
        self.build()
        total = self.steps if steps is None else steps
        t0 = time.time()
        if self.elastic and self.snapshot_every and self._snapshots is None:
            # arm the buddy store before the first step: a death in the
            # first interval recovers to here instead of dying diskless
            self._take_snapshot(self.start_step)
        try:
            step_fn = self._step_exec if self._step_exec is not None \
                else self.step_fn
            step = self.start_step
            while step < total:
                if self._rejoin_at is not None and step >= self._rejoin_at:
                    self.rejoin(step)
                    step_fn = self.step_fn
                if self.injector is not None:
                    f = self.injector.fires("rank_down", step)
                    if f is not None:
                        step = self._recover_rank_down(
                            step, dead=int(f.arg), cause="rank_down")
                        step_fn = self.step_fn
                        continue
                batch = self._next_batch(step)
                if self.injector is not None:
                    f = self.injector.fires("slow_step", step)
                    if f is not None:
                        # simulated preemption stall: the run survives it,
                        # the event log shows where the time went
                        dur = f.arg or 0.05
                        self.events.append("slow_step", step, sleep_s=dur)
                        time.sleep(dur)
                # the watchdog measures dispatch -> results materialized;
                # the first step after a (re)jit compiles, so it is exempt
                armed = self.watchdog is not None and not self._fresh_program
                if armed:
                    self.watchdog.arm(step)
                new_state, metrics = step_fn(self.state, batch)
                metrics = dict(metrics)
                if self.injector is not None:
                    f = self.injector.fires("step_hang", step)
                    if f is not None:
                        # a hung collective: a ring peer died mid-permute
                        # and this step never completes on the survivors —
                        # simulated as a stall past the watchdog deadline
                        dur = f.arg or (1.5 * self.watchdog.timeout_s
                                        if self.watchdog else 0.1)
                        self.events.append("inject", step, site="step_hang",
                                           sleep_s=dur)
                        time.sleep(dur)
                if armed:
                    float(metrics["loss"])    # block until the step is done
                    over = self.watchdog.expired()
                    if over is not None:
                        # indistinguishable from a dead peer on the ring:
                        # presume the highest rank dead and recover (its
                        # results never land, so drop this step's output)
                        self.events.append(
                            "step_hang", step, elapsed_s=over,
                            timeout_s=self.watchdog.timeout_s)
                        self._log(f"step {step}: exceeded the "
                                  f"{self.watchdog.timeout_s:.1f}s deadline "
                                  f"({over:.1f}s) — presuming a dead peer")
                        step = self._recover_rank_down(
                            step, dead=self._n_data - 1, cause="step_hang")
                        step_fn = self.step_fn
                        continue
                self._fresh_program = False
                if self.injector is not None:
                    new_state, metrics = self._inject_step_faults(
                        step, new_state, metrics)
                if self.guard is not None and \
                        not self._healthy(step, metrics):
                    if self._bad_streak >= self.guard_max_bad:
                        step = self._rollback(step)
                    else:
                        # skip the bad update: keep params/opt, advance the
                        # step counter — under CDP's uniform-staleness rules
                        # this is one more bounded delay, not a divergence
                        self.state = self._bump_step(self.state)
                        step += 1
                    continue
                self.state = new_state
                if step % self.log_every == 0 or step == total - 1:
                    rec = {"step": step,
                           "loss": float(metrics["loss"]),
                           "lr": float(metrics["lr"])}
                    self.history.append(rec)
                    self._log(f"step {step:5d}  loss {rec['loss']:.4f}  "
                              f"lr {rec['lr']:.4f}  {time.time()-t0:.1f}s")
                if self.ckpt_dir and (step + 1) % self.ckpt_every == 0:
                    self._save_checkpoint(step + 1)
                if self.elastic and self.snapshot_every and \
                        (step + 1) % self.snapshot_every == 0:
                    self._take_snapshot(step + 1)
                step += 1
        finally:
            if total >= self.steps:
                self.close()
        # never move the resume pointer backwards: a later run() with a
        # smaller target must not re-train completed steps
        self.start_step = max(self.start_step, total)
        return self.state

    def _inject_step_faults(self, step, new_state, metrics):
        import jax
        import jax.numpy as jnp
        f = self.injector.fires("nan_loss", step)
        if f is not None:
            # a real NaN gradient poisons the whole update, not just the
            # reported loss — corrupt both so an unguarded run genuinely
            # diverges
            poison = lambda x: x * jnp.nan \
                if jnp.issubdtype(x.dtype, jnp.inexact) else x
            new_state = dict(new_state)
            new_state["params"] = jax.tree.map(poison, new_state["params"])
            metrics["loss"] = float("nan")
            self.events.append("inject", step, site="nan_loss")
        f = self.injector.fires("loss_spike", step)
        if f is not None:
            factor = f.arg or 1e3
            metrics["loss"] = float(metrics["loss"]) * factor
            self.events.append("inject", step, site="loss_spike",
                               factor=factor)
        return new_state, metrics

    def _healthy(self, step, metrics) -> bool:
        loss = float(metrics["loss"])
        verdict = self.guard.check(loss)
        if verdict == "ok":
            self._bad_streak = 0
            return True
        self._bad_streak += 1
        self.events.append("skip", step, reason=verdict, loss=loss,
                           streak=self._bad_streak)
        self._log(f"step {step}: {verdict} loss ({loss}) — skipping the "
                  f"update (streak {self._bad_streak}/{self.guard_max_bad})")
        return False
