"""The draws every traffic kind shares: request sizes and token ids.

Every seed gets the SAME set of sizes in another order: the sizes are the
evenly spaced quantiles of the stated uniform ranges (so a run of any seed
sees the mix the traffic file states, not a sample of it), and the seed
decides only the order they are sent in and the token ids.  Runs of different
seeds then differ like two runs of one seed.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np


def uniform_quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """n whole numbers spread evenly over [lo, hi]."""
    return np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int)


def size_pool(prompt_range, output_range, n: int) -> List[Tuple[int, int]]:
    """n (prompt length, output length) pairs.  The pairing is a fixed
    permutation (not the seed's), so long prompts do not go with long
    outputs in every run."""
    prompts = uniform_quantiles(*prompt_range, n)
    outputs = uniform_quantiles(*output_range, n)
    outputs = outputs[np.random.RandomState(7).permutation(n)]
    return list(zip(prompts.tolist(), outputs.tolist()))


def rng_of(seed: int, stream: int) -> np.random.RandomState:
    """One numpy stream per purpose; the driver's seeds pass 2**31."""
    return np.random.RandomState([int(seed) % (1 << 32), stream])


def sized_requests(seed: int, pool, vocab: int
                   ) -> Iterator[Tuple[np.ndarray, int]]:
    """Endless (prompt ids, output length): the pool in a seeded order, over
    and over (each pass newly shuffled), ids uniform over the vocabulary."""
    order_rng, id_rng = rng_of(seed, 1), rng_of(seed, 2)
    while True:
        for i in order_rng.permutation(len(pool)):
            n_prompt, n_out = pool[i]
            yield id_rng.randint(0, vocab, n_prompt).astype(np.int32), n_out
