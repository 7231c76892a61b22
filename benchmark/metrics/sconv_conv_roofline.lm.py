"""Roofline share of `lm/attn/sconv_conv`: the reference's `sconv_conv`
work (the two elementwise gates and the taps of every gated short
convolution, the three arrays read and the one written in bfloat16,
forward and backward) against the device time under the scope
(`benchmark/lib/roofline.py`): what a fused form has to beat."""

from benchmark.lib import roofline


def read(observed):
    return roofline.share(observed, "sconv_conv", "lm/attn/sconv_conv")
