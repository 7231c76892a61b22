"""Device milliseconds an iteration under `gan/G`: the generator's
forward and backward in the G step, its forward again in the D step."""

from benchmark.lib import step_scopes


def read(observed):
    return step_scopes.under(observed, ("gan/G",))
