"""Padded lanes over lanes run in the window (the engine's counters)."""


def read(observed):
    run = observed.get("lanes_run")
    if not run:
        return None
    return 100.0 * observed.get("lanes_padded", 0) / run
