"""The benchmark's own copy of the training traffic's generator.

A training mix is read by the program's corpus (``repro.data``), which is
part of the path under test. This copy, made from the same description,
lets the harness check that each batch the program's loader hands over is
the batch the mix describes, and gives the reference its batches without
anything the program made.
"""
from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, rows: int, seq: int, vocab: int, *,
          structured: bool, noise: float) -> dict:
    """Rows of ``seq + 1`` tokens from (seed, step): a random first token,
    then, when ``structured``, the affine successor (t * 31 + 7) mod vocab;
    a share ``noise`` of positions is replaced by uniform tokens. Inputs
    are the first ``seq`` tokens, targets the last ``seq``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0, step]))
    if not structured:
        toks = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
    else:
        toks = np.empty((rows, seq + 1), dtype=np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=rows)
        for i in range(1, seq + 1):
            toks[:, i] = (toks[:, i - 1] * 31 + 7) % vocab
        corrupt = rng.random((rows, seq + 1)) < noise
        toks[corrupt] = rng.integers(0, vocab, size=int(corrupt.sum()))
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
