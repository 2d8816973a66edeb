"""The one general traffic generator.  A traffic mix is a data file of
parameters under ``traffic/``; this module turns one, with a seed, into the
run's work: today training batches.  Every seed offers the same amount of
work (the same shapes); the seed draws the token ids.
"""

import numpy as np

# ------------------------------------------------------------------ training
def zipf_cdf(vocab, exponent):
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


class TokenBatches:
    """Seeded next-token batches: ``[B, S]`` ``input_ids`` and ``labels``
    (the ids shifted by one), token ids Zipf-distributed over the vocabulary
    so that there is something to learn (a unigram distribution with entropy
    well under ln V)."""

    def __init__(self, traffic, vocab, seed):
        dist = traffic["token_dist"]
        if dist["kind"] != "zipf":
            raise ValueError(f"unknown token_dist kind {dist['kind']!r}")
        self.cdf = zipf_cdf(vocab, float(dist["exponent"]))
        self.vocab = vocab
        self.shape = (int(traffic["micro_batch"]) * int(
            traffic.get("grad_accum", 1)), int(traffic["seq_len"]) + 1)
        self.seed = int(seed)

    def batch(self, step):
        rng = np.random.default_rng([self.seed, int(step)])
        ids = np.searchsorted(self.cdf, rng.random(self.shape)).astype(np.int32)
        ids = np.minimum(ids, self.vocab - 1)
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
