"""Traffic generators: every input a run sends is drawn from its seed.

One generator serves every traffic mix; a mix is a data file of parameters
(``bench/traffic/<mix>.json``).  Each seed gets the same amount of work: an
open loop sends the same multiset of inter-arrival gaps under every seed, in
another order, so that seeds differ in arrangement and not in load.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent numpy generator for ``stream`` under ``seed``."""
    return np.random.default_rng([int(seed), *stream])


def jax_key(seed: int):
    """A JAX key from any non-negative seed, high bits included (JAX's own
    ``PRNGKey`` keeps only the low 32 bits of a Python int)."""
    import jax

    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 32) & 0xFFFFFFFF)


def open_loop_schedule(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Send offsets (s) of a Poisson open loop at ``rate_per_s`` over ``seconds``.

    The gaps are the exponential distribution's quantiles at the midpoints of
    ``n = rate * seconds`` equal bins, shuffled by the seed: every seed sends
    ``n`` requests with the same gaps, bursts and lulls in a different order.
    The first request is due at 0 and the last before ``seconds``.
    """
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    gaps *= seconds / gaps.sum()
    rng(seed, 1).shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def prompts(seed: int, n: int, length: int, vocab: int) -> np.ndarray:
    """``(n, length)`` int32 token ids, uniform over the vocabulary."""
    return rng(seed, 2).integers(0, vocab, (n, length), dtype=np.int32)


def zipf_tokens(seed: int, index: int, batch: int, seq: int, vocab: int) -> np.ndarray:
    """Batch ``index`` of a training stream: ids drawn from a Zipf law over
    the vocabulary, p(k) ~ 1/(k+1), the shape of natural-language token
    frequencies (the same law as the program's ``train.data.synthetic_batch``)."""
    p = 1.0 / np.arange(1, vocab + 1)
    r = rng(seed, 3, index)
    return r.choice(vocab, size=(batch, seq), p=p / p.sum()).astype(np.int32)


def sample(seed: int, n: int, k: int) -> list[int]:
    """``k`` distinct indices of ``range(n)`` drawn from the seed."""
    return sorted(int(i) for i in rng(seed, 4).permutation(n)[:k])
