"""Open-loop traffic on the wall clock, from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) gives an arrival process, a rate, a
pre-roll,
a prompt-length mixture (lengths and weights) and an output-length
distribution (``lognormal`` with a median, a sigma and clip bounds, or
``uniform`` between two bounds). For a run of ``seconds`` it yields one
request for every arrival in the pre-roll plus the window.

Every seed gets the same multiset of work: the inter-arrival gaps are the
exponential distribution's quantiles at (i + 1/2)/N, the prompt lengths
come in the mixture's exact proportions, and the output lengths are their
distribution's quantiles. Each of the three lists is dealt, in rank order,
into blocks of ``BLOCK`` requests (rank i to block i mod the number of
blocks), so every block holds a sample of each distribution from top to
bottom; the seed shuffles the requests within each block and the order
of the blocks, and draws the prompt tokens. So runs with different seeds
offer the same load, also over any few seconds, and differ in its order;
one seed always gives one schedule.

This arrival process is ``stratified_exponential``: exponential gaps, but
smoother than Poisson, since every ``BLOCK`` arrivals span about
``BLOCK / rate`` seconds and no cluster of arrivals or of long requests
forms. Bursts are left to a bursty mix of their own.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

BENCH = Path(__file__).resolve().parent
ARRIVALS = "stratified_exponential"
BLOCK = 10                # requests per block of the dealt mix


@dataclasses.dataclass(frozen=True)
class Arrival:
    uid: int
    due_s: float          # seconds after the traffic starts
    prompt: np.ndarray    # (prompt_len,) int32
    max_new: int


def load_traffic(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_per_s"]
                            * (traffic["preroll_s"] + seconds))))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def prompt_lengths(traffic: dict, n: int) -> np.ndarray:
    """The mixture's lengths in exact proportions (largest remainder)."""
    lens = traffic["prompt"]["lengths"]
    w = np.asarray(traffic["prompt"]["weights"], float)
    want = w / w.sum() * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(lens, int), counts)


def output_lengths(traffic: dict, n: int) -> np.ndarray:
    out = traffic["output"]
    u = _quantiles(n)
    if out["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = out["median"] * np.exp(out["sigma"] * z)
        return np.clip(np.rint(x), out["min"], out["max"]).astype(int)
    if out["dist"] == "uniform":
        return np.floor(out["min"] + u * (out["max"] - out["min"] + 1)) \
            .astype(int)
    raise ValueError(f"unknown output distribution {out['dist']!r}")


def _dealt(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` dealt by rank into blocks of about ``block``, shuffled
    within each block and in block order."""
    nb = -(-len(values) // block)
    ranked = np.sort(values)
    blocks = [rng.permutation(ranked[b::nb]) for b in range(nb)]
    return np.concatenate([blocks[b] for b in rng.permutation(nb)])


def schedule(traffic: dict, seed: int, seconds: float,
             vocab_size: int) -> List[Arrival]:
    """Every request of a run, in due order."""
    if traffic["arrivals"] != ARRIVALS:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = n_requests(traffic, seconds)
    rng = np.random.default_rng(seed)
    gaps = _dealt(-np.log1p(-_quantiles(n)) / traffic["rate_per_s"], BLOCK,
                  rng)
    lens = _dealt(prompt_lengths(traffic, n), BLOCK, rng)
    outs = _dealt(output_lengths(traffic, n), BLOCK, rng)
    due = np.cumsum(gaps) - gaps[0]
    return [Arrival(uid=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab_size, size=int(lens[i]),
                                        dtype=np.int32),
                    max_new=int(outs[i]))
            for i in range(n)]


def mean_output(traffic: dict) -> float:
    return float(np.mean(output_lengths(traffic, 4096)))


def summary(traffic: dict) -> str:
    o = traffic["output"]
    return (f"rate {traffic['rate_per_s']}/s, prompts "
            f"{traffic['prompt']['lengths']} w {traffic['prompt']['weights']}"
            f", outputs {o['dist']} {o.get('median', '')} "
            f"[{o['min']}, {o['max']}] mean {mean_output(traffic):.1f}")
