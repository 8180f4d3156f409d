"""Populations of the benchmark's deployments, made from the seed.

Copies of ``repro.data.synthetic.kdd_like`` and ``susy_like`` (the same
mixtures), kept here so that the inputs cannot change under a later change
to the program, and drawn in float32 so that the 5M-row SUSY population
takes a few seconds of set-up.  Each returns ``(x float32 (n, d),
planted_ids int64 sorted)``.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use of the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64,
                                                         stream]))


def kdd_like(rng: np.random.Generator, *, n: int, d: int,
             t_frac: float, small_clusters: int = 20):
    """KDD Cup 1999 10%-shaped records: 3 dominant classes (normal,
    neptune, smurf) hold 1 - t_frac of the mass, ``small_clusters`` small
    attack clusters the rest (the planted outliers); z-normalized."""
    big = np.array([0.196, 0.216, 0.568])
    big = big / big.sum() * (1.0 - t_frac)
    fracs = np.concatenate([big, np.full(small_clusters,
                                         t_frac / small_clusters)])
    centers = rng.normal(0.0, 2.0, size=(fracs.size, d)).astype(np.float32)
    scales = rng.uniform(0.2, 1.0, size=fracs.size).astype(np.float32)
    counts = np.maximum((fracs * n).astype(int), 1)
    counts[0] += n - counts.sum()
    labels = np.repeat(np.arange(fracs.size), counts)
    rng.shuffle(labels)
    x = rng.standard_normal((n, d), dtype=np.float32)
    x *= scales[labels, None]
    x += centers[labels]
    x -= x.mean(0)
    x /= x.std(0) + np.float32(1e-9)
    return x, np.flatnonzero(labels >= 3).astype(np.int64)


def susy_like(rng: np.random.Generator, *, n: int, d: int, planted: int,
              delta: float):
    """SUSY-shaped records (the paper's susy-Delta): a z-normalized
    two-component mixture, with ``planted`` rows shifted by
    U[-delta, delta]^d."""
    mu = rng.normal(0.0, 1.0, size=(2, d)).astype(np.float32)
    comp = rng.integers(0, 2, size=n)
    x = rng.standard_normal((n, d), dtype=np.float32)
    x += mu[comp]
    x -= x.mean(0)
    x /= x.std(0) + np.float32(1e-9)
    ids = np.sort(rng.choice(n, size=planted, replace=False))
    x[ids] += rng.uniform(-delta, delta, size=(planted, d)).astype(np.float32)
    return x, ids


GENERATORS = {"kdd_like": kdd_like, "susy_like": susy_like}


def population(spec: dict, seed: int):
    """The population a configuration's ``population`` entry names."""
    args = dict(spec)
    gen = GENERATORS[args.pop("generator")]
    return gen(rng_for(seed, 0), **args)
