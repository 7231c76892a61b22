"""Full-width one-step sweep, image group (spade, pix2pixHD, unit): every
shipped config of these families that the family-representative test in
tests/test_config_variants.py does not already step. One file per group
so that a ``--dist loadfile`` run spreads the sweep over its workers."""

import pytest

from project_configs import step_one, sweep_cases


@pytest.mark.projects_full
@pytest.mark.parametrize("rel", sweep_cases('spade', 'pix2pixHD', 'unit'))
def test_project_config_steps_full(rel, rng, tmp_path):
    step_one(rel, rng, tmp_path)
