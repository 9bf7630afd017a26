"""Training input: a few distinct batches of token ids from the seed, made
on the host and cycled for the window.  Every row of every batch differs
(ids are independent draws), so a step that leaves rows out shows.

Parameters (the traffic file): ``distinct_batches``.
"""
import numpy as np


def batches(traffic, seed, batch, seq, vocab_size):
    """``[distinct_batches, batch, seq]`` int64 token ids."""
    rng = np.random.default_rng([int(seed), 0x7A1])
    n = int(traffic.get("distinct_batches", 4))
    return rng.integers(0, vocab_size, (n, batch, seq)).astype(np.int64)
