"""The one generator of the benchmark's traffic. A mix is a data file in
bench/traffic/<name>.json; this module turns it and a seed into work.

Serving mixes (``"driver": "serve"``) are open-loop: every request has a
due time fixed before the run, whatever the server does. The sizes and the
due times of a mix come from its own ``shape_seed``, so every run serves
the same requests at the same times; ``--seed`` draws every token. A session is a run of turns; turn
j's prompt is the system prompt, the earlier turns and stand-in answers,
and the new user turn, so it extends turn j-1's prompt. ``"unique": true``
keeps the lengths and due times and fills every prompt with fresh tokens.

Training mixes (``"driver": "train"``) give the batch shape; batch ``i``
of a seed is a pure function of (seed, i): rows of a Markov chain.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, *salt])


# -- serving -----------------------------------------------------------------

@dataclasses.dataclass
class Turn:
    due: float               # seconds after the window opens
    prompt: np.ndarray       # int32 tokens
    max_gen: int
    session: int
    turn: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    v = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(v), 1, spec["cap"]).astype(int)


def _arrivals(rng, rate: float, cv: float, horizon: float) -> np.ndarray:
    """Gamma inter-arrival times of mean 1/rate and the given coefficient
    of variation, up to ``horizon`` seconds."""
    shape = 1.0 / (cv * cv)
    scale = 1.0 / (rate * shape)
    n = int(rate * horizon * 3 + 50)
    t = np.cumsum(rng.gamma(shape, scale, n))
    return t[t < horizon]


def _session_shapes(mix: dict, horizon: float) -> List[dict]:
    """Session start times and every length, from the mix's own seed: the
    same for every run of the mix."""
    s = mix["sessions"]
    rng = rng_for(mix["shape_seed"])
    mean_turns = (s["turns"][0] + s["turns"][1]) / 2.0
    starts = _arrivals(rng, mix["rate_rps"] / mean_turns,
                       mix["arrival_cv"], horizon)
    shapes = []
    zipf = 1.0 / np.arange(1, s["system_prompts"] + 1) ** s["zipf_s"]
    zipf /= zipf.sum()
    for t0 in starts:
        n = int(rng.integers(s["turns"][0], s["turns"][1] + 1))
        gaps = rng.uniform(s["think_s"][0], s["think_s"][1], n - 1)
        shapes.append({
            "start": float(t0),
            "dues": [float(t0)] + list(t0 + np.cumsum(gaps)),
            "system": int(rng.choice(s["system_prompts"], p=zipf)),
            "user": _lognormal(rng, s["user_len"], n),
            "answer": _lognormal(rng, s["answer_len"], n),
            "stand_in": _lognormal(rng, s["answer_len"], n),
        })
    return shapes


def serve_requests(mix: dict, vocab: int, seed: int, horizon: float,
                   salt: int = 0) -> List[Turn]:
    """Every turn due in [0, horizon), sorted by due time. ``salt`` gives
    another token stream (the warm-up's), with the same shapes."""
    s = mix["sessions"]
    shapes = _session_shapes(mix, horizon)
    rng = rng_for(seed, 1, salt)
    cap = mix["prompt_cap"]
    systems = [rng.integers(0, vocab, s["system_len"], dtype=np.int32)
               for _ in range(s["system_prompts"])]
    turns = []
    for k, sh in enumerate(shapes):
        ctx = systems[sh["system"]]
        for j, due in enumerate(sh["dues"]):
            user = rng.integers(0, vocab, sh["user"][j], dtype=np.int32)
            prompt = np.concatenate([ctx, user])[-cap:]
            if mix.get("unique"):
                prompt = rng.integers(0, vocab, len(prompt), dtype=np.int32)
            if due < horizon:
                turns.append(Turn(due, prompt, int(sh["answer"][j]), k, j))
            ans = rng.integers(0, vocab, sh["stand_in"][j], dtype=np.int32)
            ctx = np.concatenate([ctx, user, ans])
    turns.sort(key=lambda t: (t.due, t.session, t.turn))
    return turns


# -- training ----------------------------------------------------------------

def markov_batch(mix: dict, vocab: int, seed: int, index: int
                 ) -> Dict[str, np.ndarray]:
    """Batch ``index`` of the seed's stream: ``batch`` rows of ``seq + 1``
    Markov tokens (``states`` states, each owning a band of the vocab),
    split into inputs and next-token targets."""
    d = mix["data"]
    k = d["states"]
    trans = rng_for(seed, 2).dirichlet(np.full(k, d["concentration"]), k)
    cum = np.cumsum(trans, 1)
    rng = rng_for(seed, 3, index)
    B, T = mix["batch"], mix["seq"] + 1
    band = vocab // k
    s = rng.integers(0, k, B)
    u = rng.random((T, B))
    off = rng.integers(0, max(1, band // 4), (T, B))
    toks = np.empty((T, B), np.int64)
    for t in range(T):
        s = np.minimum((cum[s] < u[t][:, None]).sum(1), k - 1)
        toks[t] = s * band + off[t]
    toks = (toks.T % vocab).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
