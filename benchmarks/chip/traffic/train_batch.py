"""Training batches of synthetic tokens, seeded per (seed, step).

A copy of the program's synthetic stream (``repro.data.pipeline``),
kept with the benchmark so that no later change to the program changes
the inputs: a random walk over the vocabulary with uniform resets, so
that the loss is learnable.  Parameters come from the traffic file:
``seq_len``, ``global_batch`` and ``reset_prob``.
"""

from __future__ import annotations

import numpy as np


def _tokens(rng: np.random.Generator, shape, vocab: int,
            reset_prob: float) -> np.ndarray:
    walk = rng.integers(0, vocab, size=shape, dtype=np.int64)
    out = np.cumsum(walk, axis=-1) % vocab
    resets = rng.random(shape) < reset_prob
    return np.where(resets, walk, out).astype(np.int32)


def batch(p: dict, seed: int, step: int, vocab: int) -> dict:
    """Tokens and next-token labels, (global_batch, seq_len) int32 each.
    Every (seed, step) gives different rows."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    stream = _tokens(rng, (p["global_batch"], p["seq_len"] + 1), vocab,
                     p["reset_prob"])
    return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}
