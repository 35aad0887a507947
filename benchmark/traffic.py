"""The one traffic generator: a mix is a data file under
``benchmark/traffic/`` and this module turns it, with ``--seed``, into
the inputs of a run. The same seed gives the same inputs; another
seed gives the same *sizes* with other contents (and, for requests,
another starting place in the same cycle), so that the seed never
changes the amount of work.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed), stream]))


# -- training: labelled images held on the host -----------------------

def images(seed: int, n: int, size: int, channels: int,
           pattern_classes: int, noise_std: float = 0.5,
           chunk: int = 128, threads: int = 4):
    """``n`` float32 images of noise plus a per-class pattern (a
    colour per quadrant) and their labels: learnable, so a few SGD
    steps move the loss, and every row differs. Chunk ``i`` of the
    noise draws from its own stream, so a few threads can fill the
    array, and the first rows are the same however many are made."""
    rs = rng_for(seed, 11)
    pattern = rs.standard_normal(
        (pattern_classes, 2, 2, channels), dtype=np.float32)
    y = rs.integers(0, pattern_classes, size=(n, 1)).astype(np.int32)
    x = np.empty((n, size, size, channels), np.float32)
    half = size // 2

    def fill(i: int):
        rows = slice(i * chunk, min((i + 1) * chunk, n))
        part = x[rows]
        rng_for(seed, 1000 + i).standard_normal(
            part.shape, dtype=np.float32, out=part)
        part *= np.float32(noise_std)
        part += np.repeat(np.repeat(pattern[y[rows, 0]], half, 1),
                          half, 2)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(-(-n // chunk))))
    return x, y


# -- generation: requests of a closed or open loop --------------------

def _quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` sizes at the evenly spaced quantiles of the spec's
    distribution, clipped to its range: the same multiset every run."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(sizes, spec["min"], spec["max"]).astype(np.int64)


def size_pool(mix: dict) -> "list[tuple[int, int]]":
    """The mix's fixed pool of (prompt, output) sizes: quantiles of
    both distributions, paired by a permutation fixed in the file,
    each pair trimmed to the context."""
    n = int(mix["pool"])
    prompts = _quantile_sizes(mix["prompt_tokens"], n)
    outputs = _quantile_sizes(mix["output_tokens"], n)
    order = np.random.Generator(
        np.random.PCG64(int(mix["pairing_seed"]))).permutation(n)
    pool = []
    for p, o in zip(prompts, outputs[order]):
        p = int(min(p, mix["context_max"] - int(o)))
        pool.append((p, int(o)))
    return pool


def requests(mix: dict, seed: int, vocab: int) -> "list[dict]":
    """The run's request stream: the pool in the mix's own fixed
    cyclic order, entered at a seed-drawn place, each request with
    seed-drawn token ids uniform over the vocabulary. The clients
    take the stream's requests in turn and start over at its end, so
    every seed meets the same neighbours in the same order and only
    the phase against the window differs (a seed-drawn permutation
    changed which prompts share a prefill, and with it the tokens per
    second, by 2%: PERF.md)."""
    pool = size_pool(mix)
    cycle = np.random.Generator(np.random.PCG64(
        [int(mix["pairing_seed"]), 1])).permutation(len(pool))
    rs = rng_for(seed, 21)
    order = np.roll(cycle, -int(rs.integers(len(pool))))
    out = []
    for i in order:
        p, o = pool[i]
        out.append({"prompt": rs.integers(0, vocab, size=p).tolist(),
                    "max_new_tokens": o,
                    "temperature": float(mix["temperature"])})
    return out
