"""Token data of a cross-silo fleet, from a seed: `num_clients` silos,
each holding `sequences` sequences of `seq_len` + 1 token ids (the inputs
and, shifted by one, the labels). Ids follow a Zipf law of exponent
`zipf_exponent` over the `vocab_size` ids, ranked under a permutation of
the silo's own, so that each silo's frequent tokens differ (non-i.i.d.).
That set is silo i's fixed f_i.
"""
import numpy as np


def make(cfg: dict, seed: int) -> dict:
    m, n_seq, length = cfg["num_clients"], cfg["sequences"], cfg["seq_len"]
    vocab = cfg["vocab_size"]
    rng = np.random.default_rng(seed)
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -cfg["zipf_exponent"]
    cdf = np.cumsum(weights / weights.sum())
    tokens = np.empty((m, n_seq, length + 1), np.int32)
    for i in range(m):
        ranks = np.searchsorted(cdf, rng.random((n_seq, length + 1)))
        tokens[i] = rng.permutation(vocab).astype(np.int32)[
            np.minimum(ranks, vocab - 1)]
    return {"tokens": tokens}
