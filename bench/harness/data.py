"""Data made from ``--seed``.

The benchmark's own generator, shaped like the paper's synthetic
stand-ins: standard-normal f32 feature columns (with one-hot blocks for
the financial sets), labels drawn from a planted w*.  Everything derives
from one ``numpy.random.SeedSequence``, so the same seed gives the same
inputs on every machine, for seeds far above 2**32.
"""
from __future__ import annotations

import concurrent.futures

import numpy as np

#: row blocks of the feature draw (fixed, so the data do not depend on
#: the machine's core count)
CHUNKS = 8

# child streams of the run's SeedSequence, by purpose
STREAM_DATA, STREAM_KEY = range(2)


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), which]))


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey``, derived from ``seed``."""
    return int(stream(seed, STREAM_KEY).integers(0, 2 ** 31 - 1))


def _normal(seed: int, rows: int, cols: int) -> np.ndarray:
    """(rows, cols) standard-normal f32, drawn in :data:`CHUNKS` row
    blocks, each from its own stream of the seed, on as many threads."""
    x = np.empty((rows, cols), np.float32)
    cuts = np.linspace(0, rows, CHUNKS + 1).astype(int)

    def fill(k):
        rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), STREAM_DATA, k]))
        rng.standard_normal(out=x[cuts[k]:cuts[k + 1]], dtype=np.float32)

    with concurrent.futures.ThreadPoolExecutor(CHUNKS) as pool:
        list(pool.map(fill, range(CHUNKS)))
    return x


def features(seed: int, rng: np.random.Generator, rows: int, cols: int,
             onehot_frac: float) -> np.ndarray:
    """(rows, cols) f32: standard-normal columns, the last ``onehot_frac``
    of them replaced by one-hot blocks of width 4 to 8, each such column
    scaled to zero mean and unit variance."""
    x = _normal(seed, rows, cols)
    lo = cols - int(cols * onehot_frac)
    while lo < cols:
        wd = min(int(rng.integers(4, 9)), cols - lo)
        hot = rng.integers(0, wd, size=rows)
        block = np.zeros((rows, wd), np.float32)
        block[np.arange(rows), hot] = 1.0
        block -= block.mean(0)
        block /= block.std(0) + 1e-6
        x[:, lo:lo + wd] = block
        lo += wd
    return x


def labels(rng: np.random.Generator, x: np.ndarray,
           noise: float = 0.8) -> np.ndarray:
    """±1 labels from a planted, mostly dense w* (logistic link)."""
    d = x.shape[1]
    w_star = rng.standard_normal(d, dtype=np.float32)
    w_star *= rng.random(d) < 0.9
    logits = (x @ w_star) / np.float32(np.sqrt(d) * noise)
    p = 1.0 / (1.0 + np.exp(-logits))
    return np.where(rng.random(x.shape[0]) < p, 1.0, -1.0).astype(np.float32)


def dataset(cfg: dict, seed: int):
    rng = stream(seed, STREAM_DATA)
    x = features(seed, rng, cfg["rows"], cfg["cols"],
                 cfg.get("onehot_frac", 0.0))
    return x, labels(rng, x)


def party_bounds(cols: int, parties: int):
    """Vertical split of ``cols`` into ``parties`` contiguous blocks at
    the cuts ``linspace(0, cols, parties + 1)``."""
    cuts = np.linspace(0, cols, parties + 1).astype(int)
    return [(int(cuts[p]), int(cuts[p + 1])) for p in range(parties)]
