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

A file may name ``tenants`` (ids with shares, or Zipf shares over ``n``);
their labels are dealt in the same way, in exact proportions, from a
stream of the seed's own, so the rest of the schedule is the same with or
without them. Without the key every request is tenant ``t0``'s.

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
DEFAULT_TENANT = "t0"
TENANT_STREAM = 1         # the tenants' draws: a stream of their own


@dataclasses.dataclass(frozen=True)
class Arrival:
    uid: int
    due_s: float          # seconds after the traffic starts
    prompt: np.ndarray    # (prompt_len,) int32
    max_new: int
    tenant: str = DEFAULT_TENANT


def load_traffic(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_per_s"]
                            * (traffic["preroll_s"] + seconds))))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _in_proportion(values, weights, n: int) -> np.ndarray:
    """``n`` of ``values`` in the exact proportions of ``weights`` (largest
    remainder)."""
    w = np.asarray(weights, float)
    want = w / w.sum() * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(values, int), counts)


def prompt_lengths(traffic: dict, n: int) -> np.ndarray:
    """The mixture's lengths in exact proportions (largest remainder)."""
    return _in_proportion(traffic["prompt"]["lengths"],
                          traffic["prompt"]["weights"], n)


def tenants(traffic: dict) -> dict:
    """{tenant id: share of the requests}: the file's ``tenants``, either
    ``ids`` with their ``shares`` or ``n`` tenants ``t0``.. with Zipf shares
    of exponent ``zipf_s`` (tenant i's share in proportion to
    1/(i+1)^s); without the key, one tenant ``t0`` sends everything."""
    spec = traffic.get("tenants")
    if spec is None:
        return {DEFAULT_TENANT: 1.0}
    if "zipf_s" in spec:
        ids = [f"t{i}" for i in range(spec["n"])]
        w = (np.arange(spec["n"]) + 1.0) ** -float(spec["zipf_s"])
    else:
        ids, w = list(spec["ids"]), np.asarray(spec["shares"], float)
    if not ids or len(set(ids)) != len(ids) or len(w) != len(ids) \
            or (w <= 0).any():
        raise ValueError(f"tenants {spec!r}: need distinct ids, one "
                         f"positive share each")
    return dict(zip(ids, (w / w.sum()).tolist()))


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
    shares = tenants(traffic)
    ids = list(shares)
    who = _dealt(_in_proportion(range(len(ids)), list(shares.values()), n),
                 BLOCK, np.random.default_rng([seed, TENANT_STREAM]))
    return [Arrival(uid=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab_size, size=int(lens[i]),
                                        dtype=np.int32),
                    max_new=int(outs[i]), tenant=ids[who[i]])
            for i in range(n)]


def mean_output(traffic: dict) -> float:
    return float(np.mean(output_lengths(traffic, 4096)))


def summary(traffic: dict) -> str:
    o = traffic["output"]
    return (f"rate {traffic['rate_per_s']}/s, prompts "
            f"{traffic['prompt']['lengths']} w {traffic['prompt']['weights']}"
            f", outputs {o['dist']} {o.get('median', '')} "
            f"[{o['min']}, {o['max']}] mean {mean_output(traffic):.1f}"
            + (f", tenants {tenants(traffic)}" if "tenants" in traffic
               else ""))
