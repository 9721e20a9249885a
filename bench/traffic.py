"""Seeded traffic: lengths, arrival gaps and token ids for serving cells.

Every seed gets the same set of sizes and the same set of arrival gaps;
the seed changes only their order and the token ids.  Sizes and gaps come
in blocks: each block of ``block`` requests holds one value at each of the
quantiles ``(j + 0.5) / block`` of the distribution, so any run that
serves some blocks' worth of requests does the same work whatever its
seed, and two runs differ by their order alone.
"""

from __future__ import annotations

import statistics
import zlib
from typing import Iterator, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def rng(seed: int, tag: str) -> np.random.Generator:
    """An independent stream for ``(seed, tag)``; any non-negative seed,
    including ones wider than 32 bits."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, zlib.crc32(tag.encode())]
    return np.random.Generator(np.random.PCG64(np.random.SeedSequence(words)))


def sub_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for APIs that take one (``jax.random.PRNGKey``)."""
    return int(rng(seed, tag).integers(0, 2**31 - 1))


def lognormal_quantiles(block: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """``block`` whole lengths at the block's quantiles, clipped to [lo, hi]."""
    z = np.array([_NORMAL.inv_cdf((j + 0.5) / block) for j in range(block)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_quantiles(block: int, mean: float) -> np.ndarray:
    """The block's exponential gaps, scaled so that their mean is exactly
    ``mean``: every block of arrivals spans the same time."""
    u = (np.arange(block) + 0.5) / block
    q = -np.log1p(-u)
    return q * (mean / q.mean())


def length_pairs(spec: dict) -> List[Tuple[int, int]]:
    """The block's (prompt, output) pairs.  Outputs are paired with
    prompts by a fixed permutation, the same for every seed."""
    block = spec["block"]
    p, o = spec["prompt"], spec["output"]
    prompts = lognormal_quantiles(block, p["median"], p["sigma"], p["min"], p["max"])
    outputs = lognormal_quantiles(block, o["median"], o["sigma"], o["min"], o["max"])
    outputs = outputs[rng(0, "pairing").permutation(block)]
    return [(int(a), int(b)) for a, b in zip(prompts, outputs)]


class RequestStream:
    """Endless stream of requests ``(prompt_tokens, max_new)``: blocks of
    the fixed pairs, each block in its own seeded order."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        self.pairs = length_pairs(spec)
        self._order = rng(seed, "order")
        self._tokens = rng(seed, "tokens")
        self.vocab = vocab
        self._queue: List[Tuple[int, int]] = []

    def __iter__(self) -> Iterator[Tuple[np.ndarray, int]]:
        return self

    def __next__(self) -> Tuple[np.ndarray, int]:
        if not self._queue:
            perm = self._order.permutation(len(self.pairs))
            self._queue = [self.pairs[i] for i in perm[::-1]]
        prompt, out = self._queue.pop()
        tokens = self._tokens.integers(0, self.vocab, prompt, dtype=np.int64)
        return tokens.astype(np.int32), out


class ArrivalSchedule:
    """Open-loop due times (seconds from the start of traffic) of a
    Poisson process at ``rate`` per second: the block's exponential gaps,
    each block in its own seeded order."""

    def __init__(self, rate: float, block: int, seed: int):
        if not rate > 0:
            raise ValueError(f"arrival rate must be positive, got {rate}")
        self.gaps = exponential_quantiles(block, 1.0 / rate)
        self._order = rng(seed, "arrivals")
        self._queue: List[float] = []
        self.t = 0.0

    def next_due(self) -> float:
        if not self._queue:
            self._queue = list(self.gaps[self._order.permutation(len(self.gaps))][::-1])
        self.t += self._queue.pop()
        return self.t


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default)."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))
