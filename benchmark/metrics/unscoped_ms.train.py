"""Device milliseconds an iteration of the step programs' whole
executions under no scope that `step_scopes.SCOPE` reads: the busy time
less the matched time."""

from benchmark.lib import step_scopes


def read(observed):
    return step_scopes.unscoped_ms(observed)
