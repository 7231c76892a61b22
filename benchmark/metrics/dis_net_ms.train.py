"""Device milliseconds an iteration under `gan/D`: the discriminator's
forward and backward in the D step, and in the G step, where the
generator's gradient passes through it."""

from benchmark.lib import step_scopes


def read(observed):
    return step_scopes.under(observed, ("gan/D",))
