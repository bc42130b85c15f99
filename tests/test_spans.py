"""The engines' profiler spans, the programs' named scopes and the Pallas
kernels' names (repro.engine.spans): what the benchmark's trace readers
find in a traced run.

Spans are read back from a ``jax.profiler`` trace recorded here; scopes
from the compiled programs' ``op_name`` metadata; kernel names from the
``pallas_call`` equations of each kernel's jaxpr."""
import bisect
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import Request, RunSpec, ServePolicy, TrainEngine
from repro.engine import spans
from repro.engine.serve import ServeEngine
from repro.kernels import ops

SPEC = RunSpec(arch="stablelm-1.6b", reduced=True, mesh_data=1,
               mesh_model=1)


def _host_spans(log_dir, names):
    """(name, start_ns, end_ns) of the host events called one of
    ``names`` in the trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in ln.events if ev.name in names]
    return sorted(out, key=lambda s: s[1])


def _nested_in(inner, outer):
    """Every span of ``inner`` lies inside one of ``outer``."""
    starts = [s for _, s, _ in outer]
    for _, s, e in inner:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > outer[i][2]:
            return False
    return True


@pytest.fixture(scope="module")
def paged_engine():
    eng = ServeEngine(SPEC, batch=2, prompt_len=12, gen=6, verbose=False,
                      paged=True, kv_block_size=4, kv_pool_blocks=40)
    eng.build()
    return eng


def _requests(vocab, due, rid0):
    rng = np.random.default_rng(rid0)
    return [Request(rid=rid0 + i, prompt=list(rng.integers(0, vocab, 10)),
                    max_gen=4, arrival_time=t, temperature=0.0,
                    on_token=lambda *a: None)
            for i, t in enumerate(due)]


def test_serve_spans_nest_under_the_iteration_and_count_the_calls(
        paged_engine, tmp_path):
    vocab = paged_engine.cfg.vocab_size
    pol = ServePolicy(max_slots=2, prefill_chunk=4, clock="wall")
    paged_engine.serve(_requests(vocab, [0.0, 0.0], 0), policy=pol)  # warm
    # the third request arrives after the first two have finished, so the
    # loop waits for it
    with jax.profiler.trace(str(tmp_path)):
        res = paged_engine.serve(_requests(vocab, [0.0, 0.0, 3.0], 10),
                                 policy=pol)
    got = _host_spans(str(tmp_path), set(spans.SERVE_SPANS))
    by = {n: [s for s in got if s[0] == n] for n in spans.SERVE_SPANS}
    assert {n for n, v in by.items() if v} == set(spans.SERVE_SPANS)
    for n in spans.SERVE_SPANS:
        if n not in ("serve.iter", "serve.finish"):
            assert _nested_in(by[n], by["serve.iter"]), n
    assert not _nested_in(by["serve.finish"], by["serve.iter"])
    m = res["metrics"]
    assert len(by["serve.decode"]) == m["decode_steps"] > 0
    assert len(by["serve.prefill"]) == m["prefill_calls"] > 0
    assert [r.status for r in res["requests"]] == ["ok"] * 3


def test_train_run_opens_one_step_span_a_step(tmp_path):
    eng = TrainEngine(SPEC, rule="cdp_v2", steps=3, batch=2, seq=16,
                      log_every=10, verbose=False)
    eng.build()
    with jax.profiler.trace(str(tmp_path)):
        eng.run(3)
    got = _host_spans(str(tmp_path), set(spans.TRAIN_SPANS))
    assert [s[0] for s in got] == ["train.step"] * 3
    # one after another, none inside another
    assert all(a[2] <= b[1] for a, b in zip(got, got[1:]))


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _under(names, scope):
    pat = re.compile(r"[/(]%s[/)]" % scope)
    return [n for n in names if pat.search(n)]


def test_train_step_hlo_carries_the_update_and_head_scopes():
    eng = TrainEngine(SPEC, rule="cdp_v2", steps=1, batch=2, seq=16,
                      verbose=False)
    names = _op_names(eng.hlo_text())
    assert set(spans.SCOPES) >= {"update", "head"}
    assert _under(names, "update")
    # forward and backward of the head both carry the scope
    head = _under(names, "head")
    assert any("transpose(" in n for n in head)
    assert any("transpose(" not in n for n in head)


def test_sampler_ops_carry_the_sample_scope():
    from repro.engine.serve import _sampler
    B, V = 2, 64
    text = jax.jit(_sampler()).lower(
        jnp.zeros((B, V)), jax.random.split(jax.random.PRNGKey(0), B),
        jnp.zeros((B,)), jnp.zeros((B,), jnp.int32)).compile().as_text()
    assert _under(_op_names(text), "sample")


def _kernel_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    out += _kernel_names(sub)
    return out


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, interpret=True)


def _grad_flash(q, k, v):
    return jax.grad(lambda *a: _flash(*a).sum(), argnums=(0, 1, 2))(q, k, v)


def _paged(fn):
    def call(q, k, v):
        B, H, D = q.shape[0], q.shape[2], q.shape[3]
        pool = jnp.zeros((5, H, 4, D))      # 4 blocks of 4 and the trash
        table = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
        if fn == "decode":
            return ops.paged_decode_attention(
                q[:, :1], pool, pool, table, jnp.array([5, 7], jnp.int32),
                interpret=True)
        return ops.paged_prefill_attention(
            q[:, :4], pool, pool, table, jnp.zeros((B,), jnp.int32),
            jnp.array([4, 4], jnp.int32), interpret=True)
    return call


def _decode(q, k, v):
    return ops.decode_attention(q[:, :1], k, v, jnp.array([5, 7], jnp.int32),
                                interpret=True)


def _gla(q, k, v):
    g = -jnp.ones(q.shape[:3])
    return jax.grad(lambda q: ops.gla_scan(q, k, v, g, chunk=8,
                                           interpret=True).sum())(q)


@pytest.mark.parametrize("fn,names", [
    (_flash, ["flash_attention_fwd"]),
    (_grad_flash, ["flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"]),
    (_paged("decode"), ["paged_decode_attention"]),
    (_paged("prefill"), ["paged_prefill_attention"]),
    (_decode, ["decode_attention"]),
    (_gla, ["gla_scan_fwd", "gla_scan_bwd"]),
], ids=["flash_fwd", "flash_bwd", "paged_decode", "paged_prefill",
        "decode", "gla_scan"])
def test_every_pallas_kernel_has_a_name(fn, names):
    q = jnp.ones((2, 16, 2, 8), jnp.float32)
    assert sorted(_kernel_names(jax.make_jaxpr(fn)(q, q, q).jaxpr)) == \
        sorted(names)
