"""BERT pretraining batch: ids, MLM labels on a seeded 15% of positions
(-100 elsewhere, the loss's ignore_index) and NSP labels.  No padding
mask: every position is a real token."""
import numpy as np


def make(seed: int, batch: int, seq: int, sizes: dict) -> tuple:
    """(ids (B,S), mlm_labels (B,S), nsp_labels (B,)), all int32."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes["vocab_size"], size=(batch, seq))
    masked = rng.random((batch, seq)) < sizes["mlm_probability"]
    mlm = np.where(masked, ids, -100)
    nsp = rng.integers(0, 2, size=(batch,))
    return (ids.astype(np.int32), mlm.astype(np.int32),
            nsp.astype(np.int32))
