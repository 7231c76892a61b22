"""Device milliseconds an iteration under the `step/...` scopes of both
step programs (`BaseTrainer._gen_step_fn`, `_dis_step_fn`): the casts to
the compute type, the clip, the optimizer's update, the finite guard, the
averaged generator, the health norms; forward and backward together."""

from benchmark.lib import step_scopes


def read(observed):
    return step_scopes.under(observed, ("step/",))
