"""A seeded corpus of packed token sequences in the program's format
(`imaginaire_tpu/data/packed_tokens.py`: a `.npy` of (sequences, seq_len)
int32), made once per checkout and found again by a stamp.

Documents: lengths log-normal (median and sigma from the cell's traffic
file, clipped to the sequence length), ids Zipf over the vocabulary
slice's ids 1.., each document closed by the end-of-document id; the
documents are concatenated and cut into sequences with no padding.
"""

from __future__ import annotations

import os
import shutil

import numpy as np


def zipf_ids(rng, n, exponent, ids):
    """`n` ids in [1, ids): rank r (1 the most frequent) with probability
    proportional to r^-exponent; id 0 is the end-of-document's."""
    ranks = np.arange(1, ids, dtype=np.float64)
    cdf = np.cumsum(ranks ** -exponent)
    cdf /= cdf[-1]
    return (np.searchsorted(cdf, rng.random_sample(n)) + 1).astype(np.int32)


def packed_tokens(base, traffic):
    """The directory holding the fixture `traffic` describes, built if its
    stamp is not there."""
    docs, ids = traffic["document_tokens"], traffic["token_ids"]
    n, seq_len = int(traffic["fixture_sequences"]), int(traffic["seq_len"])
    seed = int(traffic["content_seed"])
    stamp = os.path.join(base, ".stamp_{}_{}_{}_{}_{}_{}_{}".format(
        n, seq_len, docs["median"], docs["sigma"], ids["exponent"],
        ids["ids"], seed))
    if os.path.exists(stamp):
        return base
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    rng = np.random.RandomState(seed)
    total = n * seq_len
    stream = zipf_ids(rng, total, float(ids["exponent"]), int(ids["ids"]))
    end = 0
    while end < total:
        length = int(np.clip(rng.lognormal(np.log(docs["median"]),
                                           docs["sigma"]), 2, docs["clip"]))
        end += length
        if end <= total:
            stream[end - 1] = int(traffic["end_of_document_id"])
    np.save(os.path.join(base, "tokens.npy"), stream.reshape(n, seq_len))
    open(stamp, "w").close()
    return base
