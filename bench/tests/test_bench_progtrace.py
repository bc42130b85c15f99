"""The program-span readers (bench/progtrace.py and the metrics that use
it) on plain intervals and synthetic op events, and the span loader on a
trace recorded here."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import devtrace as tr  # noqa: E402
import harness  # noqa: E402
import progtrace as pt  # noqa: E402

harness.program_path()
E = tr.Event


def _reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("_m_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_innermost_cuts_the_window_by_the_innermost_span():
    spans = [E("serve.iter", 10, 50), E("serve.sync", 20, 10),
             E("serve.decode", 40, 15), E("serve.iter", 70, 10)]
    assert pt.innermost(spans, 0, 100) == [
        (0, 10, "none"), (10, 20, "serve.iter"), (20, 30, "serve.sync"),
        (30, 40, "serve.iter"), (40, 55, "serve.decode"),
        (55, 60, "serve.iter"), (60, 70, "none"), (70, 80, "serve.iter"),
        (80, 100, "none")]
    # clipped to the window
    assert pt.innermost(spans, 25, 45) == [
        (25, 30, "serve.sync"), (30, 40, "serve.iter"),
        (40, 45, "serve.decode")]


def test_overlap_and_idle_by_piece():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert pt.overlap(a, b) == 5 + 5 + 5
    pieces = [(0, 8, "x"), (8, 22, "y"), (22, 60, "x")]
    parts = pt.cut(a, pieces)
    assert parts == [(0, 8, "x"), (8, 10, "y"), (20, 22, "y"),
                     (22, 30, "x"), (40, 50, "x")]
    assert pt.totals(parts) == {"x": 8 + 8 + 10, "y": 2 + 2}


def test_idle_is_the_complement_of_the_busy_union():
    evs = [E("a", 10, 20), E("b", 25, 10), E("c", 60, 100)]
    assert pt.busy(evs, 0, 100) == [(10, 35), (60, 100)]
    assert pt.idle(evs, 0, 100) == [(0, 10), (35, 60)]


def test_ops_found_by_the_program_execution_holding_them():
    evs = [E("%a", 0, 10, "%a"), E("%b", 10, 10, "%b"),
           E("%c", 20, 10, "%c"), E("%d", 30, 10, "%d")]
    mods = [E("jit_step(7)", 0, 12, "jit_step(7)"),
            E("jit_step_active(8)", 12, 6, "jit_step_active(8)"),
            E("jit_admit_fresh(9)", 19, 12, "jit_admit_fresh(9)")]
    assert [e.name for e in pt.in_programs(evs, mods, ("step",))] == ["%a"]
    assert [e.name for e in pt.in_programs(
        evs, mods, ("step", "step_active"))] == ["%a", "%b"]
    assert [e.name for e in pt.in_programs(evs, mods, ("admit_fresh",))
            ] == ["%c"]
    assert pt.in_programs(evs, [], ("step",)) == []
    assert [m.start for m in pt.executions(mods, ("step", "admit_fresh"))
            ] == [0, 19]


def _ctx(prog, events, lo, hi, result):
    """A reader's context over synthetic device events and program spans
    (the program trace already read, as ``progtrace.of`` keeps it)."""
    busy = tr.total(pt.busy(events, lo, hi))
    red = tr.Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9, op_s={},
                     idle_gaps=[], exposed_collective_s=0.0, events=events)
    return {"progtrace": prog, "trace": red, "result": result}


SERVE_RESULT = {"metrics": {"itl_p95_ms": 105.0, "serve_out_tok_s": 12.6},
                "counters": {"decode_steps": 2}}


def _serve_ctx():
    # window 0..1000: arrivals wait 0..300 (idle), a chunk 300..400 under
    # admit_shared, then two decode steps
    spans = [E("serve.iter", 0, 300), E("serve.wait", 10, 280),
             E("serve.iter", 300, 200), E("serve.prefill", 300, 20),
             E("serve.decode", 480, 10),
             E("serve.iter", 500, 400), E("serve.sync", 500, 150),
             E("serve.emit", 650, 100), E("serve.decode", 750, 20),
             E("serve.finish", 900, 90)]
    mods = [E("jit_admit_shared(1)", 310, 100, "jit_admit_shared(1)"),
            E("jit_step_active(2)", 490, 150, "jit_step_active(2)"),
            E("jit_step_active(3)", 760, 150, "jit_step_active(3)")]
    ops = [E("%chunk", 310, 100, "%chunk"),
           E("%layer", 490, 140, "%layer"),
           E("%sort", 630, 10, "%sort jit(step_active)/sample/sort"),
           E("%layer", 760, 130, "%layer"),
           E("%sort", 890, 10, "%sort jit(step_active)/sample/sort")]
    prog = pt.ProgTrace(spans, (0.0, 1000.0), mods)
    return _ctx(prog, ops, 0, 1000, SERVE_RESULT)


def test_host_idle_and_wait_idle_make_up_device_idle(capsys):
    ctx = _serve_ctx()
    device_idle = _reader("device_idle.serve")(ctx)
    host = _reader("host_idle.serve")(ctx)
    # idle: 0..310, 410..490, 640..760, 900..1000 = 610 of 1000; the wait
    # span covers 280 of it
    assert device_idle == pytest.approx(61.0)
    assert host == pytest.approx(33.0)
    idle = pt.idle(ctx["trace"].events, 0, 1000)
    waits = pt.busy(pt.of(ctx).named("serve.wait"), 0, 1000)
    assert host + 100.0 * pt.overlap(idle, waits) / 1000 == \
        pytest.approx(device_idle, abs=1e-9)
    # the longest stretch outside serve.wait is 650..750 under serve.emit;
    # per decode step, over 2 steps: 640..650 under serve.sync; 480..490
    # and 750..760 under serve.decode
    err = capsys.readouterr().err
    assert "0.000 0.0000 none" not in err
    assert "host idle without its longest stretch: 23.0% " in err
    assert "serve.sync 5e-06 ms, serve.decode 1e-05 ms" in err


def test_prefill_share_of_busy_time(capsys):
    ctx = _serve_ctx()
    # busy 390: admission 100 of it
    assert _reader("prefill_share.serve")(ctx) == pytest.approx(
        100.0 * 100 / 390)
    assert "1e-07 s of device time, 1 calls, " in \
        capsys.readouterr().err


def test_readers_read_nothing_from_a_program_without_spans():
    ops = [E("%a", 0, 500, "%a")]
    prog = pt.ProgTrace([], (0.0, 1000.0), [])
    ctx = _ctx(prog, ops, 0, 1000, SERVE_RESULT)
    assert _reader("host_idle.serve")(ctx) is None
    # admission share needs no span: nothing of an admission program here
    assert _reader("prefill_share.serve")(ctx) is None


def test_load_reads_the_program_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            for s in range(2):
                with jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=s):
                    f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("serve.sync"):
            pass                              # a span after the window
        with jax.profiler.TraceAnnotation("not.a.span"):
            pass

    class Tracer:
        path = str(tmp_path)
    ctx = {"tracer": Tracer()}
    got = pt.of(ctx)
    assert got.window is not None
    steps = got.named("train.step", *got.window)
    assert len(steps) == 2 and len(got.named("serve.sync")) == 1
    assert not got.named("serve.sync", *got.window)
    assert {s.name for s in got.spans} == {"train.step", "serve.sync"}
    assert pt.of(ctx) is got                  # read once a run
