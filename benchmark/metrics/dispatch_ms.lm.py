"""The host's enqueue of the one step program of a token model: the
median `gen_step` span (fingerprint included). `dispatch_ms.train` adds a
`dis_step` span that such a trainer never opens."""

from benchmark.lib import program_spans


def read(observed):
    return program_spans.median_ms("gen_step")
