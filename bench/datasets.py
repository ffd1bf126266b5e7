"""Key sets of the benchmark's deployments, made from a seed.

The paper evaluates on four real 200M-key datasets chosen by hardness
(arXiv:2306.02604 §5.1.2, Table 1). The files are not in the repository, so
the benchmark generates stand-ins with the shape of the two hard ones. A
stand-in's hardness is its own, not the real file's: its FMCD conflict
degree (``repro.core.fmcd.dataset_conflict_degree``) is measured apart.

* ``osm``: OSM-like cell ids, shaped like the paper's C4 "globally hard"
  set (the real file's conflict degree is 4106): clusters of 6,000 |Cauchy|
  draws of scale 1e3, 1e5 or 1e7 around uniform centres below 2^60, with
  huge empty stretches between them.
* ``genome``: GENOME-like loci, shaped like the paper's C3 "locally hard"
  set (the real file's degree is 585): runs of gaps of 1 to 3 from uniform
  centres below 2^38.

The shapes are those of the repository's ``core/workloads.py``. The OSM
draw is the one ``make_dataset("osm")`` ends with at any size (its first,
smaller draw always falls short and is repeated 1.6 times larger): n / 2,500
clusters, keeping the n smallest unique keys. The draws are vectorized over
fixed blocks of clusters, each with its own stream spawned from the seed,
and the blocks run on a few threads; the keys depend on the seed alone.
The key sets belong to the benchmark, so no change to the program can move
them. ``VERSION`` changes whenever the keys a seed gives change.
"""
from __future__ import annotations

import concurrent.futures
import os

import numpy as np

VERSION = 1
BLOCK_CLUSTERS = 512
THREADS = max(1, min(12, os.cpu_count() or 1))


def _osm_block(rng: np.random.Generator, centers: np.ndarray,
               per: int) -> np.ndarray:
    scales = rng.choice(np.array([1e3, 1e5, 1e7]), centers.size)
    u = rng.random((centers.size, per), dtype=np.float32)
    u -= np.float32(0.5)
    u *= np.float32(np.pi)
    c = np.abs(np.tan(u))                      # |standard Cauchy|
    x = c.astype(np.float64)
    x *= scales[:, None]
    x += centers[:, None]
    x.sort(axis=1)
    x = x.reshape(-1)
    return x[x < 2.0 ** 62].astype(np.uint64)


def _genome_block(rng: np.random.Generator, centers: np.ndarray,
                  per: int) -> np.ndarray:
    steps = rng.integers(1, 4, (centers.size, per), dtype=np.int64)
    np.cumsum(steps, axis=1, out=steps)
    x = steps.astype(np.float64)
    x += centers[:, None]
    return x.reshape(-1).astype(np.uint64)


# generator -> (block function, keys per cluster, draws per cluster, span)
GENERATORS = {
    "osm": (_osm_block, 2500, 6000, 2.0 ** 60),
    "genome": (_genome_block, 2000, None, 2.0 ** 38),
}


def _draw(generator: str, n: int, ss: np.random.SeedSequence) -> np.ndarray:
    block, keys_per, per, span = GENERATORS[generator]
    k = max(n // keys_per, 8)
    if per is None:
        per = int(n * 1.1) // k
    head, *block_ss = ss.spawn(1 + -(-k // BLOCK_CLUSTERS))
    centers = np.sort(np.random.default_rng(head).uniform(0, span, k))
    starts = range(0, k, BLOCK_CLUSTERS)
    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(
            lambda a: block(np.random.default_rng(a[1]),
                            centers[a[0]:a[0] + BLOCK_CLUSTERS], per),
            zip(starts, block_ss)))
    keys = np.concatenate(parts)
    del parts
    # clusters come in centre order and each is sorted, so only tails that
    # reach past the next centre are out of order: the run-aware sort is
    # near linear here
    keys.sort(kind="stable")
    keep = np.empty(keys.size, dtype=bool)
    keep[:1] = True
    np.not_equal(keys[1:], keys[:-1], out=keep[1:])
    return keys[keep]


def make_keys(generator: str, n: int, seed: int) -> np.ndarray:
    """``n`` sorted unique u64 keys of ``generator`` from ``seed``; a draw
    that falls short of ``n`` unique keys is repeated 1.6 times larger."""
    if generator not in GENERATORS:
        raise ValueError(f"unknown key generator {generator!r}; "
                         f"known: {sorted(GENERATORS)}")
    ss = np.random.SeedSequence([VERSION, int(seed) % 2 ** 64])
    req = n
    for attempt in ss.spawn(4):
        keys = _draw(generator, req, attempt)
        if keys.size >= n:
            return keys[:n].copy() if keys.size > n else keys
        req = int(req * 1.6)
    raise RuntimeError(f"{generator}: {keys.size} unique keys < {n}")
