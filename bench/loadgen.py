"""The one traffic generator: a mix file (``traffic/<mix>.json``) and a
seed in, a list of requests out.

Every seed gets the same multiset of sizes and gaps, drawn once from a
fixed stream (``SIZES_SEED``); the run's seed only orders them and draws
the token ids. Runs with different seeds then do the same work, and
their spread is the system's, not the draw's.

Open loop (``"loop": "open"``): ``round(rate * (preroll + seconds))``
Poisson gaps, scaled to span exactly ``preroll + seconds``; ``due`` is
seconds from the window's start (negative during the preroll). An
optional set of system prompts is shared by requests, picked Zipf(s).
Closed loop (``"loop": "closed"``): ``requests`` requests that the
harness keeps ``concurrency`` of in flight; they are ordered within
blocks of ``concurrency``, so the first block is the same multiset for
every seed. ``due`` is 0 for all of them.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
SIZES_SEED = 20251017


@dataclasses.dataclass
class Request:
    due: float
    prompt: np.ndarray
    max_new: int
    system: int = -1  # index of its shared system prompt (-1: none)


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    elif spec["dist"] == "lognormal":
        x = np.round(spec["median"] * np.exp(spec["sigma"]
                                             * rng.standard_normal(n)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def zipf_weights(count: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return w / w.sum()


def generate(mix: dict, seed: int, seconds: float, vocab: int):
    """Requests of ``mix`` for a window of ``seconds``, in due order."""
    fixed = np.random.default_rng(SIZES_SEED)
    rng = np.random.default_rng(int(seed))
    if mix["loop"] == "open":
        span = mix["preroll_s"] + seconds
        n = max(1, int(round(mix["rate_per_s"] * span)))
        gaps = fixed.exponential(1.0 / mix["rate_per_s"], n)
        gaps *= span / gaps.sum()
        user = _lengths(mix["prompt"], n, fixed)
        out = _lengths(mix["output"], n, fixed)
        sp = mix.get("system_prompts")
        if sp:
            lens = fixed.integers(sp["min"], sp["max"] + 1, sp["count"])
            lens -= lens % sp["align"]
            pick = fixed.choice(sp["count"], n,
                                p=zipf_weights(sp["count"], sp["zipf_s"]))
        else:
            lens, pick = np.zeros(0, np.int64), np.full(n, -1)
        order = rng.permutation(n)
        due = np.cumsum(rng.permutation(gaps)) - mix["preroll_s"]
        heads = [rng.integers(0, vocab, k).astype(np.int32) for k in lens]
        reqs = []
        for i, j in enumerate(order):
            body = rng.integers(0, vocab, user[j]).astype(np.int32)
            head = heads[pick[j]] if pick[j] >= 0 else body[:0]
            reqs.append(Request(float(due[i]), np.concatenate([head, body]),
                                int(out[j]), int(pick[j])))
        return reqs
    if mix["loop"] == "closed":
        n, c = mix["requests"], mix["concurrency"]
        plen = _lengths(mix["prompt"], n, fixed)
        out = _lengths(mix["output"], n, fixed)
        order = np.concatenate([b + rng.permutation(min(c, n - b))
                                for b in range(0, n, c)])
        return [Request(0.0, rng.integers(0, vocab, plen[j]).astype(np.int32),
                        int(out[j])) for j in order]
    raise ValueError(f"unknown loop {mix['loop']!r}")
