"""Host time to enqueue one iteration's two step programs, pytree
fingerprint included: the median of the program's `dis_step` span plus
the median of its `gen_step` span."""

from benchmark.lib import program_spans


def read(observed):
    dis = program_spans.median_ms("dis_step")
    gen = program_spans.median_ms("gen_step")
    if dis is None or gen is None:
        return None
    return dis + gen
