"""Causal-LM batch: random token ids, used as their own labels."""
import numpy as np


def make(seed: int, batch: int, seq: int, sizes: dict) -> tuple:
    """(ids, labels), both (batch, seq) int32 below ``vocab_size``."""
    ids = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], size=(batch, seq)).astype(np.int32)
    return ids, ids
