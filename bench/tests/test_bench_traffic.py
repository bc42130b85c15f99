"""The traffic generator (bench/traffic.py): deterministic in the seed,
within the mixes' length caps, sharing what the sessions mix shares and
nothing in the unique mix, with the same arrivals for every seed."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import traffic  # noqa: E402

VOCAB = 65024
SEEDS = (7, 2 ** 31 + 12345)


def turns(name, seed, horizon=60.0):
    return traffic.serve_requests(traffic.load_mix(name), VOCAB, seed,
                                  horizon)


@pytest.mark.parametrize("name", ["serve.sessions", "serve.unique"])
def test_serve_mix_is_a_function_of_the_seed(name):
    a, b, c = turns(name, SEEDS[0]), turns(name, SEEDS[0]), turns(name, 8)
    assert [(t.due, t.max_gen) for t in a] == [(t.due, t.max_gen) for t in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c) if len(x.prompt) == len(y.prompt))


@pytest.mark.parametrize("name", ["serve.sessions", "serve.unique"])
def test_serve_mix_lengths_and_dues(name):
    mix = traffic.load_mix(name)
    for seed in SEEDS:
        ts = turns(name, seed)
        assert ts and all(0 <= t.due < 60.0 for t in ts)
        assert [t.due for t in ts] == sorted(t.due for t in ts)
        assert all(1 <= len(t.prompt) <= mix["prompt_cap"] for t in ts)
        assert all(1 <= t.max_gen <= mix["sessions"]["answer_len"]["cap"]
                   for t in ts)
        assert all(t.prompt.min() >= 0 and t.prompt.max() < VOCAB
                   for t in ts)
    # every seed serves the same requests at the same times
    shapes = [[(t.due, len(t.prompt), t.max_gen) for t in turns(name, s)]
              for s in SEEDS]
    assert shapes[0] == shapes[1]


def test_sessions_share_prefixes():
    mix = traffic.load_mix("serve.sessions")
    ts = turns("serve.sessions", SEEDS[1])
    by = {}
    for t in ts:
        by.setdefault(t.session, []).append(t)
    grown = 0
    for sess in by.values():
        sess.sort(key=lambda t: t.turn)
        for a, b in zip(sess, sess[1:]):
            if len(b.prompt) < mix["prompt_cap"]:
                assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
                grown += 1
    assert grown > 0
    sys_len = mix["sessions"]["system_len"]
    systems = {t.prompt[:sys_len].tobytes() for t in ts if t.turn == 0}
    assert 1 < len(systems) <= mix["sessions"]["system_prompts"]


def test_unique_keeps_lengths_and_shares_nothing():
    # at one rate the two mixes hold the same requests; only tokens differ
    ses, uni = traffic.load_mix("serve.sessions"), \
        traffic.load_mix("serve.unique")
    uni["rate_rps"] = ses["rate_rps"]
    s = traffic.serve_requests(ses, VOCAB, SEEDS[0], 60.0)
    u = traffic.serve_requests(uni, VOCAB, SEEDS[0], 60.0)
    assert [(t.due, len(t.prompt), t.max_gen) for t in s] == \
        [(t.due, len(t.prompt), t.max_gen) for t in u]
    heads = [t.prompt[:16].tobytes() for t in u]
    assert len(set(heads)) == len(heads)


def test_warm_up_stream_differs_from_the_window():
    mix = traffic.load_mix("serve.sessions")
    w = traffic.serve_requests(mix, VOCAB, SEEDS[0], 60.0)
    u = traffic.serve_requests(mix, VOCAB, SEEDS[0], 60.0, salt=1)
    window_heads = {t.prompt[:16].tobytes() for t in w}
    assert not window_heads & {t.prompt[:16].tobytes() for t in u}


def test_markov_batches():
    mix = traffic.load_mix("train.cdp_v2")
    a = traffic.markov_batch(mix, 100352, SEEDS[1], 3)
    b = traffic.markov_batch(mix, 100352, SEEDS[1], 3)
    c = traffic.markov_batch(mix, 100352, SEEDS[1], 4)
    assert a["tokens"].shape == (mix["batch"], mix["seq"])
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == mix["batch"]
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 100352
