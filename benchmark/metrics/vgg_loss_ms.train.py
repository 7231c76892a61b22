"""Device milliseconds an iteration under `gan/loss/perceptual`: the
loss network's forward on the fake and the real images and its backward
to the fake ones, in the G step."""

from benchmark.lib import step_scopes


def read(observed):
    return step_scopes.under(observed, ("gan/loss/perceptual",))
