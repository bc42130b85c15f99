"""The names of the profiler spans and scopes the engines record.

Spans are host intervals on the profiler's clock
(``jax.profiler.TraceAnnotation``): they record only while a trace is
active and otherwise cost two Python calls each. They carry no arguments:
no reader reads one. Scopes (``jax.named_scope``) are traced into the
compiled programs' op metadata once and cost nothing at run time.

Tests and the benchmark's readers take the names from here.
"""
from __future__ import annotations

# ServeEngine.serve(), one scheduler iteration at a time. Every span but
# serve.finish nests inside serve.iter; a phase span opens only when its
# phase has work that iteration.
SERVE_SPANS = (
    "serve.iter",       # one loop iteration
    "serve.arrivals",   # arrivals into the queue and deadline expiry
    "serve.admit",      # admissions: pool plan, CoW copies, whole-prompt
                        # prefills, table upload
    "serve.prefill",    # the chunked-prefill batch, its table upload and
                        # dispatch
    "serve.kv_grow",    # paged: prepare_write, CoW copies, table upload
    "serve.wait",       # the sleep toward the next arrival (wall clock)
    "serve.sync",       # the host sync of sampled tokens and row health
    "serve.emit",       # emission logging, on_token and stream hooks,
                        # releases and table upload
    "serve.decode",     # the step / step_active dispatch
    "serve.finish",     # after the loop: the last block_until_ready and
                        # the token-history transfer
)

# TrainEngine.run(): one StepTraceAnnotation a step (arg step_num), which
# also fills the profiler's step view. The benchmark counts them against
# the steps a window ran.
TRAIN_SPANS = (
    "train.step",
)

# jax.named_scope names on device ops: a path component of each op's
# ``op_name`` metadata (``.../transpose(jvp(head))/...`` in a backward pass).
# TPU op events in a profiler trace carry no ``op_name``, so no reader times
# them yet: that waits for a benchmark reader that maps the compiled
# programs' HLO instructions to their ``op_name`` (PERF.md, open questions).
SCOPES = (
    "head",             # final norm, logits matmul, padded-vocab mask and
                        # the f32 cross-entropy (models/model.py)
    "update",           # the optimizer update and the stale-parameter
                        # selection (core/trainer.py, parallel/zero_cdp.py)
    "sample",           # the serving sampler (engine/serve.py)
)

