"""Properties of the cyclic schedule — the paper's Fig. 1 / Table 1 claims."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import schedule as S


@given(st.integers(2, 32))
def test_cdp_every_worker_busy_every_tick(n):
    # each worker performs exactly one F or B micro-step per tick
    for tau in range(2 * n, 4 * n):
        kinds = [S.cdp_phase(w, tau, n).kind for w in range(n)]
        assert all(k in "FB" for k in kinds)


@given(st.integers(2, 32))
def test_cdp_stage_occupancy_disjoint(n):
    # at any tick, the (kind, stage) slots across workers are all distinct:
    # each stage runs at most one forward and one backward micro-step (the
    # resource feasibility behind Fig. 1b/1c)
    for tau in range(2 * n, 4 * n):
        slots = [(S.cdp_phase(w, tau, n).kind, S.cdp_phase(w, tau, n).stage)
                 for w in range(n)]
        assert len(set(slots)) == n


@given(st.integers(2, 32))
def test_cdp_total_activations_constant(n):
    tl = S.total_activation_timeline(n, cyclic=True)
    # constant across ticks, equal to N(N+1)/2 stage-units (paper Sec. 4.1)
    assert np.allclose(tl, tl[0])
    assert tl[0] == pytest.approx(n * (n + 1) / 2)


@given(st.integers(2, 32))
def test_dp_peaks_at_n_times_n(n):
    tl = S.total_activation_timeline(n, cyclic=False)
    assert tl.max() == pytest.approx(S.dp_peak_activations(n))
    # DP peak is ~2x the CDP constant
    assert tl.max() >= 2 * S.cdp_total_activations(n) * (n / (n + 1))


@given(st.integers(2, 24))
def test_u_matrix_rules(n):
    u_dp = S.u_matrix(S.RULE_DP, n)
    u1 = S.u_matrix(S.RULE_CDP_V1, n)
    u2 = S.u_matrix(S.RULE_CDP_V2, n)
    assert u_dp.all()
    assert not u1.any()
    # v2 is elementwise fresher than v1, staler than DP
    assert (u2 >= u1).all() and (u_dp >= u2).all()
    # v2 structure: micro-batch i uses fresh params on stages >= N-1-i
    for i in range(n):
        assert u2[i, S.fresh_threshold(S.RULE_CDP_V2, i, n):].all()
        assert not u2[i, :S.fresh_threshold(S.RULE_CDP_V2, i, n)].any()
    # the last micro-batch of the cycle is fully fresh under v2
    assert u2[n - 1].all()


@given(st.integers(2, 24))
def test_delay_at_most_one_step(n):
    for rule in S.RULES:
        d = S.delay_matrix(rule, n)
        assert d.min() >= 0 and d.max() <= 1


@given(st.integers(2, 16))
@settings(deadline=None)
def test_comm_events_balanced(n):
    """CDP gradient sends are spread evenly: every tick has the same number
    of point-to-point messages (+-1), and each worker sends at most one."""
    events = S.comm_events(n)
    by_tau = {}
    for e in events:
        by_tau.setdefault(e["tau"], []).append(e)
    counts = [len(v) for v in by_tau.values()]
    assert max(counts) - min(counts) <= 1
    assert max(counts) == -(-n // 2)        # half the workers are in backward
    for v in by_tau.values():
        srcs = [e["src"] for e in v]
        assert len(set(srcs)) == len(srcs)


def test_table1_matches_paper():
    t = S.table1(n=4, B=32, Pp=100.0, Pa=10.0, Pa_int=1.0)
    assert t["single_gpu_cdp"]["act_mem"] == pytest.approx(
        (4 + 1) / 2 * 32 * 10.0)
    assert t["single_gpu_dp"]["act_mem"] == pytest.approx(4 * 32 * 10.0)
    assert t["multi_gpu_cdp"]["comm_steps"] == "O(1)"
    assert t["multi_gpu_dp"]["comm_steps"] == "O(log N)"
    assert t["dp_mp_cdp"]["gpus"] == 4 * 5 // 2
    assert t["dp_mp"]["gpus"] == 16
    assert t["dp_mp_cdp"]["volume"] < t["dp_mp"]["volume"]
