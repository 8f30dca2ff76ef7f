"""The one traffic generator: it reads a mix file from ``bench/traffic/``
and turns it, with ``--seed``, into the inputs the program receives.

Every seed gets the same multiset of sizes and gaps: each distribution is
sampled at its quantiles ``(i + 0.5) / n``, and the seed only chooses their
order and the token ids. Runs with different seeds then do the same amount
of work, so their spread is the system's and not the draw's.

Mix kinds:

* ``open_loop``: independent users. ``rate_per_s`` fixes the arrival rate;
  gaps are exponential (Poisson arrivals); ``prompt_len`` and
  ``output_len`` are length distributions. Each request is due at its
  arrival time whether or not the server keeps up.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    with open(MIX_DIR / f"{name}.json") as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose; any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths with the distribution ``spec``, in seed order."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(np.rint(x), spec.get("min", 1), spec.get("max", np.inf))
    return rng.permutation(x.astype(np.int64))


@dataclass
class Request:
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def open_loop(mix: dict, seconds: float, seed: int,
              vocab: int) -> List[Request]:
    """The requests due inside a window of ``seconds``, in due order."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = rng_for(seed, 1)
    gaps = -np.log1p(-_quantiles(n)) / mix["rate_per_s"]
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    p_len = lengths(mix["prompt_len"], n, rng_for(seed, 2))
    o_len = lengths(mix["output_len"], n, rng_for(seed, 3))
    tok = rng_for(seed, 4)
    return [Request(float(due[i]),
                    tok.integers(0, vocab, size=int(p_len[i]),
                                 dtype=np.int32),
                    int(o_len[i]))
            for i in range(n) if due[i] < seconds]


def warmup_requests(prompt_lens, vocab: int, new_tokens: int = 3):
    """One short request per prompt length, to run every program and eager
    helper the window will use before it opens."""
    rng = rng_for(0, 9)
    return [Request(0.0, rng.integers(0, vocab, size=int(n), dtype=np.int32),
                    new_tokens) for n in prompt_lens]

