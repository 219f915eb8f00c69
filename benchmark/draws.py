"""The draws every traffic kind shares: request sizes and token ids.

The sizes are the evenly spaced quantiles of the stated uniform ranges, and
the seed decides the order they are sent in and the token ids.  What a
WINDOW then sees depends on the traffic file's `order`:

- `"shuffled"` (the default): the pool of (prompt, output) pairs, newly
  shuffled each pass.  Every pass of the whole pool carries the stated mix;
  a window that sees a fraction of a pass sees a random sample of it,
  another for every seed (32 consecutive requests stray up to a fifth from
  the pool's mean length).  Good where a window sees several passes, or a
  request's cost barely depends on its size.
- `"spread"`: prompt lengths and output lengths each walk their sorted
  quantiles in a low-discrepancy order (one endless sequence, no seam
  between passes), so ANY run of consecutive requests, a window's among
  them, carries the pool's mix of both: the mean of 32 consecutive lengths
  lies within 4% of the pool's, for every seed and every place in the
  sequence.  The seed decides where the two walks start, and the ids.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np

# the fractional parts of i * step fill [0, 1) evenly at every length of
# the sequence; the two steps are not rationally related, so prompt and
# output lengths do not move together
PROMPT_STEP = (5 ** 0.5 - 1) / 2
OUTPUT_STEP = 2 ** 0.5 - 1


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


def _shuffled(order_rng, pool) -> Iterator[Tuple[int, int]]:
    while True:
        for i in order_rng.permutation(len(pool)):
            yield pool[i]


def _spread(order_rng, pool) -> Iterator[Tuple[int, int]]:
    prompts = sorted(p for p, _ in pool)
    outputs = sorted(n for _, n in pool)
    u0, v0 = order_rng.uniform(0.0, 1.0, 2)
    i = 0
    while True:
        yield (prompts[int((u0 + i * PROMPT_STEP) % 1.0 * len(pool))],
               outputs[int((v0 + i * OUTPUT_STEP) % 1.0 * len(pool))])
        i += 1


ORDERS = {"shuffled": _shuffled, "spread": _spread}


def request_sizes(seed: int, pool, order: str = "shuffled"
                  ) -> Iterator[Tuple[int, int]]:
    """Endless (prompt length, output length): the pool's sizes in the
    seeded `order` (the module's docstring)."""
    if order not in ORDERS:
        raise ValueError(f"order {order!r}: one of {sorted(ORDERS)}")
    return ORDERS[order](rng_of(seed, 1), pool)


def sized_requests(seed: int, pool, vocab: int, order: str = "shuffled"
                   ) -> Iterator[Tuple[np.ndarray, int]]:
    """`request_sizes` with the prompts' ids, uniform over the vocabulary."""
    id_rng = rng_of(seed, 2)
    for n_prompt, n_out in request_sizes(seed, pool, order):
        yield id_rng.randint(0, vocab, n_prompt).astype(np.int32), n_out
