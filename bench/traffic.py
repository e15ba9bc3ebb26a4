"""One general traffic generator, driven by a mix file (bench/mixes/<mix>.json).

Adapted from `repro.data.synthetic.traffic_requests` (seeded, open-loop,
prompt lengths quantized to the prefill chunk) and extended with lognormal
length distributions, a backlog arrival process and seed-independent work.
Kept here so that a change to the program cannot move the yardstick.

Every seed of one mix and window gets the same multiset of prompt lengths,
output lengths and inter-arrival gaps: each is drawn at the stratified
quantiles (i + 0.5) / n of its distribution. The seed picks the token ids,
how prompt and output lengths pair up, and the order in which requests
arrive. So two seeds differ in what is computed, not in how much.

Mix keys:
  slots, max_len, chunk        engine pool: slots, positions per slot, prefill
                               chunk length
  prompt, output               {"dist": "lognormal", "median", "sigma", "min",
                               "max", ["quantum"]} or {"dist": "uniform",
                               "min", "max"}; lengths are clipped to
                               [min, max] and prompts rounded to `quantum`
  arrivals                     {"process": "backlog", "requests_per_s",
                               "order": "longest_output_first" | "random"}:
                               requests_per_s x seconds requests, all due at
                               t=0; or {"process": "poisson", "rate"}: rate x
                               seconds requests at exponential gaps, the
                               first due at t=0
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the benchmark offers it to the system."""
    rid: int
    prompt: np.ndarray      # (L,) int32 token ids
    max_new: int
    arrival: float          # seconds after the window opens


def seed_words(seed: int, n: int) -> List[int]:
    """n 32-bit words derived from a seed of any size (the driver's seeds
    exceed 32 signed bits)."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return [int(w) for w in np.random.SeedSequence(seed).generate_state(n)]


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    per_s = arr["requests_per_s"] if arr["process"] == "backlog" \
        else arr["rate"]
    return max(1, int(round(per_s * seconds)))


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The multiset of n lengths a distribution spec gives (ascending)."""
    u = _strata(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        lo, hi = spec["min"], spec["max"]
        raw = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = spec.get("quantum", 1)
    out = np.round(raw / q) * q
    return np.clip(out, spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """The multiset of n exponential inter-arrival gaps at `rate` per s."""
    return -np.log1p(-_strata(n)) / rate


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Planned]:
    """The requests of one run: a fixed amount of work for the window,
    arranged by the seed."""
    n = n_requests(mix, seconds)
    rng = np.random.Generator(np.random.PCG64(seed_words(seed, 4)))
    prompts = lengths(mix["prompt"], n)
    outputs = lengths(mix["output"], n)
    if prompts.max() + outputs.max() > mix["max_len"]:
        raise ValueError("mix lengths overflow the slot: prompt max "
                         f"{prompts.max()} + output max {outputs.max()} > "
                         f"max_len {mix['max_len']}")
    if mix["chunk"] and np.any(prompts % mix["chunk"]):
        raise ValueError("prompt lengths must be whole prefill chunks")
    prompts = rng.permutation(prompts)            # pair with outputs by seed
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        if arr.get("order", "random") == "longest_output_first":
            # an offline job that sorts its prompt set: longest outputs
            # first, so the pool drains evenly; the seed orders each
            # pool-sized group of similar lengths
            idx = np.argsort(-outputs, kind="stable")
            slots = mix["slots"]
            idx = np.concatenate([rng.permutation(idx[i:i + slots])
                                  for i in range(0, n, slots)])
        else:
            idx = rng.permutation(n)
        due = np.zeros(n)
    elif arr["process"] == "poisson":
        idx = rng.permutation(n)
        g = rng.permutation(gaps(arr["rate"], n - 1))
        due = np.concatenate([[0.0], np.cumsum(g)])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    out = []
    for rid, i in enumerate(idx):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32)
        out.append(Planned(rid=rid, prompt=toks, max_new=int(outputs[i]),
                           arrival=float(due[rid])))
    return out


def chunk_lengths(planned: List[Planned], chunk: int) -> List[int]:
    """Distinct prefill chunk lengths the requests produce (warm-up
    shapes)."""
    return sorted({min(chunk, len(p.prompt) - s) for p in planned
                   for s in range(0, len(p.prompt), chunk)})
