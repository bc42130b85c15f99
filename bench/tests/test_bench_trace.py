"""The trace reduction (bench/devtrace.py) on plain intervals and on a trace
recorded here: busy-interval union, idle share, idle gaps by host span,
exposed collective time, kernel events by name."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import devtrace as tr  # noqa: E402

E = tr.Event


def test_union_merges_overlaps_and_keeps_gaps():
    iv = [(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)]
    assert tr.union(iv) == [(0, 3), (5, 8), (10, 12)]
    assert tr.total(tr.union(iv)) == 8


def test_gaps_and_clip():
    busy = [(0, 3), (5, 8), (10, 12)]
    assert tr.gaps(busy, 0, 14) == [(3, 5), (8, 10), (12, 14)]
    assert tr.gaps(busy, 1, 9) == [(3, 5), (8, 9)]
    assert tr.clip(busy, 2, 11) == [(2, 3), (5, 8), (10, 11)]


def test_exposed_collective_time():
    # collective 0..10 overlapped by compute 2..5 and 4..7: exposed 0..2
    # and 7..10; a second collective 20..25 with no compute: all exposed
    comp = [(2, 5), (4, 7), (30, 40)]
    coll = [(0, 10), (20, 25)]
    assert tr.exposed(coll, comp) == 2 + 3 + 5


def test_reduce_events_busy_idle_and_gap_labels():
    dev = {"/device:TPU:0": [
        E("fusion.1", 0, 40, "fusion.1"),
        E("fusion.2", 30, 30, "fusion.2"),             # overlaps: union 0..60
        E("all-reduce.3", 100, 50, "all-reduce.3"),     # exposed 100..120
        E("fusion.4", 120, 80, "fusion.4"),             # 120..200
    ], "/device:TPU:1": [
        E("fusion.1", 0, 100, "fusion.1"),
    ]}
    spans = [E("window", 0, 300), E("step", 0, 150), E("feed", 60, 40)]
    r = tr.reduce_events(dev, spans, 0, 300)
    assert r.window_s == pytest.approx(300e-9)
    # chip 0 busy 0..60 and 100..200; chip 1 busy 0..100; mean 130 ns
    assert r.busy_s == pytest.approx(130e-9)
    assert r.op_s["fusion.1"] == pytest.approx(140e-9)
    assert tr.first_chip(r.planes) == "/device:TPU:0"
    bare, by = tr.exposed_collectives(r.planes["/device:TPU:0"], *r.span)
    assert bare == 20 and by == {"all-reduce": 50}
    # the longest gap, 100..300 on chip 1, sits under "window" alone;
    # chip 0's 60..100 lies inside the "feed" span
    assert r.idle_gaps[0] == ("window", pytest.approx(200e-9))
    assert ("feed", pytest.approx(40e-9)) in r.idle_gaps
    assert tr.top_ops(r.op_s, 1) == [["fusion.1", pytest.approx(140e-9)]]


def test_kernel_events_by_name():
    evs = [E("custom-call.1", 0, 1, "custom-call.1 _flash_bwd_dkv_kernel"),
           E("custom-call.2", 0, 1, "custom-call.2 _flash_bwd_dq_kernel"),
           E("custom-call.3", 0, 1, "custom-call.3 _flash_kernel"),
           E("fusion.4", 0, 1, "fusion.4")]
    marks = {"dkv": ("flash_bwd_dkv",), "dq": ("flash_bwd_dq",),
             "fwd": ("_flash_kernel",)}
    got = tr.kernel_events(evs, marks)
    assert [e.name for e in got["dkv"]] == ["custom-call.1"]
    assert [e.name for e in got["dq"]] == ["custom-call.2"]
    assert [e.name for e in got["fwd"]] == ["custom-call.3"]


def test_recorded_trace_holds_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    win = tr.window_of(t.spans, "window")
    step = tr.window_of(t.spans, "step")
    assert win is not None and step is not None
    assert win[0] <= step[0] <= step[1] <= win[1]
    assert any(p.startswith("/host:") for p in t.line_names)


# op texts as a v5e trace gives them (XLA Ops line), shortened operands
FWD = ("%flash_attention.40 = (bf16[128,2048,64]{2,1,0:T(8,128)(2,1)S(1)}, "
       "f32[128,1,2048]{2,1,0:T(1,128)}) custom-call(s32[136]{0:T(256)S(1)} "
       "%copy-done.104), custom_call_target=\"tpu_custom_call\"")
DKV = ("%flash_attention.41 = (bf16[128,2048,64]{2,1,0:T(8,128)(2,1)}, "
       "bf16[128,2048,64]{2,1,0:T(8,128)(2,1)}) custom-call(s32[136]{0:T(256)}"
       " %get-tuple-element.1570), custom_call_target=\"tpu_custom_call\"")
DQ = ("%flash_attention.42 = bf16[128,2048,64]{2,1,0:T(8,128)(2,1)} "
      "custom-call(s32[136]{0:T(256)S(1)} %copy-done.105)")
LOOP = ("%while.10 = (s32[]{:T(128)}, /*index=5*/bf16[4,2048,2048]{1,2,0:T(8,"
        "128)(2,1)}) while((s32[]{:T(128)}) %tuple.170), condition=%wide")


def test_op_kinds_labels_and_containers():
    assert E("x", 0, 1, FWD).kind == "custom-call"
    assert E("x", 0, 1, DKV).label == (
        "flash_attention.41 = (bf16[128,2048,64], bf16[128,2048,64]) "
        "custom-call")
    assert E("x", 0, 1, LOOP).kind == "while"
    dev = {"/device:TPU:0": [E("x", 0, 100, LOOP), E("y", 10, 50, FWD)]}
    r = tr.reduce_events(dev, [], 0, 100)
    assert r.busy_s == pytest.approx(100e-9)
    assert list(r.op_s) == [E("y", 10, 50, FWD).label]


def test_flash_kernels_told_apart_by_their_results():
    import harness
    import importlib.util
    path = os.path.join(harness.HERE, "metrics", "flash_roofline.train.py")
    spec = importlib.util.spec_from_file_location("_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert [mod.kind_of(E("x", 0, 1, t)) for t in (FWD, DKV, DQ, LOOP)] == \
        ["fwd", "dkv", "dq", None]


def test_collectives_counted_from_hlo_text():
    import hlo
    text = """HloModule m

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %x = f32[8]{0} collective-permute(f32[8]{0} %y), source_target_pairs={{0,1}}
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(4)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%cond, body=%body
  %r = bf16[16]{0} all-reduce(bf16[16]{0} %z), to_apply=%add
}
"""
    st = hlo.parse_collectives(text)
    assert st.op_counts["collective-permute"] == 4
    assert st.by_type["collective-permute"] == 4 * 8 * 4
    assert st.op_counts["all-reduce"] == 1
    assert st.total_bytes == 128 + 32
