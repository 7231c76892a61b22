"""Roofline share of `lm/attn/q_latent`, `lm/attn/kv_latent` and
`lm/attn/rope` together: the reference's `latent_work` (operations and
bytes, every latent-attention layer, forward and backward) against the
device time under the three scopes, by `benchmark/lib/roofline.py`'s rule."""

import os

from benchmark.lib import harness, roofline

_MS = harness.load_by_path(
    os.path.join(os.path.dirname(__file__), "mla_latent_ms.lm.py"),
    "benchmark_metric_mla_latent_ms_lm")


def read(observed):
    measured_ms = _MS.read(observed)
    if measured_ms is None:
        return None
    # the three scopes' time as one scope's, for the shared rule
    together = {"seconds": {"lm/attn/latent": measured_ms / 1e3}}
    return roofline.share(dict(observed, scopes=together), "mla_latent",
                          "lm/attn/latent")
