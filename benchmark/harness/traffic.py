"""The one general generator of a cell's inputs: the cell's file gives the
parameters (`job`), the seed gives the data.  Stdlib + numpy only.

A training job is a stream of batches: `global_batch` rows of
`sequence_length` tokens, ids uniform over the vocabulary, every row and every
step different, labels the next token.  Every seed gives the same sizes, so
the seed changes the data and never the work.
"""
from __future__ import annotations

import numpy as np


def train_batch(job, vocab_size, seed, step):
    """Batch `step` of the job: ids and next-token labels, all rows
    different, from the seed."""
    rng = np.random.default_rng([int(seed), 0x7a, int(step)])
    tok = rng.integers(0, vocab_size,
                       (job["global_batch"], job["sequence_length"] + 1),
                       dtype=np.int64).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]
