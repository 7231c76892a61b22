"""The training cell's arithmetic without the program: the comparison by
the worst leaf, the FLOP count, the loop's epoch roll-over."""

import numpy as np
import pytest

from bench_rehearsal_util import ROOT, TINY  # noqa: F401

from benchmark.drivers import train_fed
from benchmark.lib import harness


def test_worst_leaf_gap_is_measured_against_the_larger_of_leaf_and_median():
    theirs = {"a": 10.0, "b": 1.0, "c": 1e-6}
    ours = {"a": 10.5, "b": 1.0, "c": 0.3}
    gap, leaf = train_fed.worst_leaf_gap(ours, theirs)
    # c is all but zero: its gap is held against the median leaf (1.0)
    assert leaf == "c" and gap == pytest.approx(0.3, rel=1e-4)
    gap, leaf = train_fed.worst_leaf_gap({**ours, "c": 1e-6}, theirs)
    assert leaf == "a" and gap == pytest.approx(0.05)


def test_compare_names_each_number_and_the_leaf_at_fault():
    ref = {"losses": [{"D": 2.0, "G": -10.0}, {"D": 1.9, "G": -9.0}],
           "first_gradient_norms": {"w": 1.0, "b": 0.5},
           "first_gradient_projections": {"w": 0.3, "b": -0.2},
           "param_change_norms": {"w": 0.2, "b": 0.1}}
    ours = {"losses": [{"D": 2.02, "G": -10.0}, {"D": 1.9, "G": -9.9}],
            "first_gradient_norms": {"w": 1.0, "b": 0.6},
            "first_gradient_projections": {"w": 0.4, "b": -0.35},
            "param_change_norms": {"w": 0.2, "b": 0.2}}
    numbers, where = train_fed.compare(ours, ref)
    assert numbers["loss_D_first_rel"] == pytest.approx(0.01)
    assert numbers["loss_G_first_rel"] == 0.0
    # the later steps' gaps are reported, not compared
    assert "loss_G_max_rel" not in numbers
    gaps = train_fed.loss_gaps(ours, ref)
    assert gaps["D"] == pytest.approx([0.01, 0.0])
    assert gaps["G"] == pytest.approx([0.0, 0.1])
    assert numbers["first_gradient_norm_worst_leaf"] == pytest.approx(
        0.1 / 0.75)
    # |0.4 - 0.3| / 1.0 and |-0.35 + 0.2| / max(0.5, median 0.75)
    assert numbers["first_gradient_apart_median_leaf"] == pytest.approx(0.15)
    assert where["param_change_norm_worst_leaf"] == "b"
    # a total whose terms cancel is held against their magnitudes
    near_zero = dict(ref, losses=[{"D": 2.0, "G": 0.03, "G_scale": 3.0}])
    off = dict(ours, losses=[{"D": 2.0, "G": 0.06}])
    numbers, _ = train_fed.compare(off, near_zero)
    assert numbers["loss_G_first_rel"] == pytest.approx(0.01)
    # a step that returns its state unchanged: the change reads 1
    frozen = dict(ours, param_change_norms={"w": 0.0, "b": 0.0})
    numbers, _ = train_fed.compare(frozen, ref)
    assert numbers["param_change_norm_worst_leaf"] == pytest.approx(1.0)


def test_change_norms_in_float64_on_the_host():
    after = {"w": np.full((4,), 1.5, np.float32)}
    initial = {"w": np.full((4,), 1.0, np.float32), "w/u": np.zeros(2)}
    assert train_fed.change_norms(after, initial) == {"w": 1.0}


def test_step_flops_counts_convolutions_and_scales_with_the_batch():
    from benchmark.reference import spade_train

    sizes = dict(harness.read_json(
        ROOT + "/benchmark/configs/spade_cocostuff_256.json")["sizes"], **TINY)
    one = spade_train.step_flops(sizes, 1)
    four = spade_train.step_flops(sizes, 4)
    assert four["iteration"] == pytest.approx(4 * one["iteration"], rel=1e-3)
    f = one["forward"]
    assert one["dis_step"] == f["G"] + 6 * f["D"]
    assert one["gen_step"] == 3 * (f["G"] + f["D"] + f["V"])
    assert one["iteration"] == one["dis_step"] + one["gen_step"]
    # VGG19 to relu_5_1 at 256x256, by hand: 2 * H*W * 9 * cin * cout
    convs = [(256, 3, 64), (256, 64, 64), (128, 64, 128), (128, 128, 128),
             (64, 128, 256), (64, 256, 256), (64, 256, 256), (64, 256, 256),
             (32, 256, 512), (32, 512, 512), (32, 512, 512), (32, 512, 512),
             (16, 512, 512)]
    assert f["V"] == sum(2 * s * s * 9 * a * b for s, a, b in convs)


class _FakeTrainer:
    def __init__(self):
        self.calls = []

    def data_prefetcher(self, loader, iteration_of=None):
        return loader

    def __getattr__(self, name):
        def call(*args):
            self.calls.append(name)
            return args[0] if name == "start_of_iteration" else {}
        return call


class _FakeLoader(list):
    def set_epoch(self, epoch):
        self.epoch = epoch


class _FakeTelemetry:
    def timed_iter(self, feed, name, step_of=None):
        return (x for x in feed)


def test_the_loop_rolls_into_the_next_epoch_as_train_py_does():
    trainer = _FakeTrainer()
    loader = _FakeLoader([{"k": 1}, {"k": 2}])
    loop = train_fed.Loop(trainer, loader, _FakeTelemetry())
    for _ in range(3):
        loop.step()
    assert loop.iteration == 3 and loop.epoch == 1 and loader.epoch == 1
    body = ["start_of_iteration", "dis_update", "gen_update",
            "end_of_iteration"]
    assert trainer.calls == (["start_of_epoch"] + body * 2
                             + ["end_of_epoch", "start_of_epoch"] + body)
    loop.close()
