"""Seconds from process start to the start of the measured window:
imports, the program's state, the seed's weights, compilation or the
cache's load, and the warm-up runs."""


def read(observed):
    return observed.get("setup_s")
