"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

Serving mixes (``"kind": "requests"``) give request counts and length
distributions. Lengths are a fixed stratified set, the same for every seed:
quantile i of n of a lognormal (median, sigma), rounded and clipped to
[min, max], with prompt and output lengths paired by a fixed permutation
and queued in a fixed shuffled order. A window serves only the head of the
queue, so the order sets its work (which requests finish in it and how
many prompts it prefills): the seed draws only the token ids, and runs
with different seeds do the same work. ``pool_pages`` sizes the KV pool
from what the set reserves, not from the longest request.

Training mixes (``"kind": "train"``) give the sequence length and the global
batch; the rows themselves come from the program's seeded token stream.
"""
from __future__ import annotations

import statistics

import numpy as np

import core


def lognormal_set(dist: dict, n: int) -> np.ndarray:
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def lengths(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prompt, output) lengths of every request, paired, in queue order."""
    n = mix["requests"]
    prompt = lognormal_set(mix["prompt"], n)
    out = lognormal_set(mix["output"], n)
    out = out[np.random.default_rng(0).permutation(n)]
    order = np.random.default_rng(1).permutation(n)
    return prompt[order], out[order]


def pool_pages(mix: dict, slots: int, page_size: int, z: float) -> int:
    """KV pages for ``slots`` requests in flight, each reserving its whole
    length up front: their mean total plus ``z`` standard deviations of a
    sum of ``slots`` draws from the set, plus the engine's null page."""
    prompt, out = lengths(mix)
    pages = -(-(prompt + out) // page_size)
    total = slots * pages.mean() + z * pages.std() * slots ** 0.5
    return int(max(np.ceil(total), pages.max())) + 1


def requests(mix: dict, seed: int, vocab: int) -> list[dict]:
    """[{"prompt": int32 array, "max_new": int}] in queue order, the
    prompts' tokens drawn from the seed."""
    prompt, out = lengths(mix)
    rng = core.seed_rng(seed, 1)
    return [{"prompt": rng.integers(0, vocab, int(p), dtype=np.int32),
             "max_new": int(o)} for p, o in zip(prompt, out)]


def max_seq_len(mix: dict) -> int:
    return int(mix["prompt"]["max"] + mix["output"]["max"])
