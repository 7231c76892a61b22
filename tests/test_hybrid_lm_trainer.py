"""The hybrid token model through the trainer, against the plain
reference (ISSUE 27 (b), (d), (f)): the whole model's loss and gradients,
two `gen_update` steps against the reference's Adam steps (both for the
latent-attention preset too, whose loss is two: ISSUE 31; and for the
delta-rule preset: ISSUE 34), an overfull
expert buffer failing the step's health flag, and a token batch passing
the feed's index-map rule untouched."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_lm_util import seeded, tiny_cfg, unflatten

from imaginaire_tpu.registry import resolve


def _tokens(cfg, seed=1, batch=2):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.gen.vocab_slice,
                       (batch, cfg.data.seq_len)).astype(np.int32)


def _trainer(cfg, train, buffers):
    from benchmark.drivers import train_lm

    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    data = {"tokens": jnp.asarray(_tokens(cfg))}
    trainer.init_state(jax.random.PRNGKey(0), data)
    # copies: the step donates its state, and the tests read the seed's
    # arrays again
    train_lm.install_weights(trainer, {
        k: jnp.array(v, copy=True) for k, v in {**train, **buffers}.items()})
    return trainer, data


def _worst(ours, theirs):
    return max(float(jnp.linalg.norm(ours[k] - theirs[k])
                     / (jnp.linalg.norm(theirs[k]) + 1e-12)) for k in theirs)


PRESETS = ["nemotron_h", "glm4_moe_lite", "solar_open2", "lfm2_moe"]


@pytest.mark.parametrize("preset", PRESETS)
def test_model_loss_and_gradients_follow_the_reference(preset):
    from benchmark.lib import program
    from imaginaire_tpu.models.generators import hybrid_lm

    cfg = tiny_cfg(preset)
    reference, sizes, train, buffers = seeded(cfg, 7, preset)
    tokens = jnp.asarray(_tokens(cfg))
    net = hybrid_lm.Generator(cfg.gen, cfg.data)
    weight = cfg.gen.get("nextn_loss_weight", 0.0)

    def ours(train):
        out = net.apply({"params": unflatten(train),
                         "buffers": unflatten(buffers)}, {"tokens": tokens})
        return out["loss"] + weight * out.get("mtp_loss", 0.0), out

    def theirs(train):
        return reference.loss(train, buffers, sizes, tokens)

    (l_ours, out), g_ours = jax.jit(jax.value_and_grad(
        ours, has_aux=True))(train)
    (l_theirs, aux), g_theirs = jax.jit(jax.value_and_grad(
        theirs, has_aux=True))(train)
    assert abs(float(l_ours) - float(l_theirs)) < 1e-5 * float(l_theirs)
    assert _worst(g_ours, g_theirs) < 1e-4
    for layer, counts in aux.items():
        # the module's layers also count the last position's assignments,
        # which the program computes and nothing reads
        spare = (tokens.shape[0] * cfg.gen.num_experts_per_tok
                 if layer >= len(cfg.gen.pattern) else 0)
        assert 0 <= float(out[f"moe/{layer}/held_assignments"]) - float(
            counts["held_assignments"]) <= spare
    # the seam's names are the program's own paths
    assert set(program.flatten(unflatten(train))) == set(train)


def test_the_module_predicts_the_token_after_the_next():
    """ISSUE 31: the two losses each follow the reference's (whose
    module's targets are `tokens[:, 2:]`); the embedding and the head are
    one array each, and the module's loss reaches both."""
    from benchmark.reference import glm4_moe_lite_train as reference
    from imaginaire_tpu.models.generators import hybrid_lm

    preset = "glm4_moe_lite"
    cfg = tiny_cfg(preset)
    _, sizes, train, buffers = seeded(cfg, 3, preset)
    tokens = jnp.asarray(_tokens(cfg))
    net = hybrid_lm.Generator(cfg.gen, cfg.data)

    def ours(train, tokens):
        out = net.apply({"params": unflatten(train),
                         "buffers": unflatten(buffers)}, {"tokens": tokens})
        return out["loss"], out["mtp_loss"]

    def theirs(train, tokens):
        return reference.losses(train, buffers, sizes, tokens)[:2]

    def each_with_its_gradient(fn):
        """((loss, its gradient), (the module's loss, its gradient)) in
        one compiled program."""
        def run(train, tokens):
            losses, vjp = jax.vjp(lambda train: fn(train, tokens), train)
            return [(loss, vjp(tuple(jnp.float32(i == which)
                                     for i in range(2)))[0])
                    for which, loss in enumerate(losses)]
        return jax.jit(run)

    ours_both = each_with_its_gradient(ours)
    found = dict(zip(("loss", "mtp_loss"), zip(
        ours_both(train, tokens),
        each_with_its_gradient(theirs)(train, tokens))))
    for name, ((l_ours, g_ours), (l_theirs, g_theirs)) in found.items():
        assert abs(float(l_ours) - float(l_theirs)) < 1e-5 * float(l_theirs)
        assert _worst(g_ours, g_theirs) < 1e-4
        for shared in ("embedding", "head"):
            assert float(jnp.abs(g_ours[shared]).max()) > 0
    # the module's own layers see only its loss
    assert float(jnp.abs(found["loss"][0][1][
        "layer_6/mixer/o_proj"]).max()) == 0
    # the last token is nobody's input in the module (position L - 2's
    # next token, whose own target is past the end), only position L - 3's
    # target: moving it moves the module's loss and leaves every logit
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % cfg.gen.vocab_slice)
    assert float(found["mtp_loss"][0][0]) != float(
        ours_both(train, moved)[1][0])


STEP_CASES = [*((preset, 2) for preset in PRESETS), ("lfm2_moe", 3)]


@functools.lru_cache(maxsize=None)
def _stepped(preset, steps):
    """One trainer and one reference step program a preset, built once
    for all of its cases: `steps` `gen_update` steps at a batch of 2
    beside the reference's Adam steps, with each side's loss and
    parameters kept after every step."""
    from benchmark.lib import program

    cfg = tiny_cfg(preset)
    reference, sizes, train, buffers = seeded(cfg, 11, preset)
    trainer, data = _trainer(cfg, train, buffers)
    assert trainer.net_D is None and trainer.tx_D is None
    assert "opt_D" not in trainer.state and trainer.dis_update(data) is None
    assert data["tokens"].shape[0] == 2

    def host(tree):
        return {k: np.array(v, copy=True) for k, v in tree.items()}

    ours = []
    for _ in range(steps):
        loss = float(trainer.gen_update(data)["total"])
        ours.append((loss, host(program.flatten(
            trainer.state["vars_G"]["params"]))))

    @jax.jit
    def step(train, mu, nu, count):
        (loss, _), grads = jax.value_and_grad(reference.loss, has_aux=True)(
            train, buffers, sizes, data["tokens"])
        return (loss,) + reference.adam(
            train, grads, mu, nu, count, cfg.gen_opt.lr,
            cfg.gen_opt.adam_beta1, cfg.gen_opt.adam_beta2)

    mu = {k: jnp.zeros_like(v) for k, v in train.items()}
    nu = dict(mu)
    theirs = []
    for count in range(steps):
        loss, train, mu, nu = step(train, mu, nu, count)
        theirs.append((float(loss), host(train)))
    after = host(program.flatten(
        trainer.state["vars_G"].get("buffers", {})))
    return ours, theirs, after, host(buffers)


@pytest.mark.parametrize("preset,steps", STEP_CASES)
def test_trainer_steps_follow_the_reference_adam(preset, steps):
    """Two `gen_update` steps at a batch of 2 against the reference's Adam
    steps; three for the short-convolution preset, as many as the
    benchmark's cell checks (ISSUE 39)."""
    longest = max([steps] + [n for name, n in STEP_CASES if name == preset])
    ours, theirs, buffers_after, buffers = _stepped(preset, longest)
    np.testing.assert_allclose([loss for loss, _ in ours[:steps]],
                               [loss for loss, _ in theirs[:steps]],
                               rtol=1e-5)
    assert _worst(ours[steps - 1][1], theirs[steps - 1][1]) < 1e-5
    # the score-correction bias is a buffer: nothing moved it
    for name, value in buffers_after.items():
        np.testing.assert_array_equal(value, buffers[name])


@pytest.mark.parametrize("yaml, batch, layers, a_layer, kernel_dim", [
    # 8,192 x 20 heads x 256 in bfloat16 and 20 x 8,192 float32 rows
    ("glm4_moe_lite/flash_ep8_share.yaml", 1, (0, 2, 4, 6, 8, 10),
     83_886_080 + 655_360, 256),
    # 8,192 x 32 heads x 128, 32 x 8,192 rows
    ("nemotron_h/nano_30b_a3b_ep16_share.yaml", 1, (5,),
     67_108_864 + 1_048_576, 128),
    # two sequences: 16,384 x 32 heads x 64 (the kernel's output cut back
    # to the published head) and 2 x 32 x 8,192 rows
    ("lfm2_moe/8b_a1b_ep4_share.yaml", 2, (2,),
     67_108_864 + 2_097_152, 128)],
    ids=["glm4_7_flash", "nemotron3_nano", "lfm2_8b_a1b"])
def test_attn_impl_counts_what_the_fused_blocks_keep(monkeypatch, yaml,
                                                     batch, layers, a_layer,
                                                     kernel_dim):
    """ISSUE 33: the `attn_impl` meta of the published configurations'
    step (sequences of 8,192) on a TPU: every attention layer fused,
    its block keeping the kernel's output and log-sum-exp under the
    YAML's `remat: blocks` and nothing under a policy that recomputes
    the kernel; on this CPU no layer is fused and none keeps a byte.
    ISSUE 39: head size 64 takes the fused arm too, the kernel at 128."""
    import os

    from hybrid_lm_util import ROOT

    from imaginaire_tpu.config import Config
    from imaginaire_tpu.telemetry.report import render_report
    from imaginaire_tpu.trainers import lm

    gen = Config(os.path.join(ROOT, "configs", "projects", yaml)).gen
    assert gen.remat == "blocks"
    gen["compute_dtype"] = "bfloat16"     # as the trainer sets it
    names = [str(i) for i in layers]
    here = lm.attn_impl(gen, (batch, 8192))
    assert here["layers"] == dict.fromkeys(names, "blocks")
    assert here["kept_bytes"] == dict.fromkeys(names, 0)
    assert here["kernel_head_dim"] == kernel_dim
    assert "backward_products" not in here      # no kernel, no sweep
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.attn_impl(gen, (batch, 8192))
    assert meta["layers"] == dict.fromkeys(names, "fused")
    assert meta["kept_bytes"] == dict.fromkeys(names, a_layer)
    # ISSUE 42: one backward sweep of five products a tile, a key-value
    # head's float32 dk and dv standing in VMEM through it
    assert meta["backward_products"] == 5
    assert meta["vmem_accumulator_bytes"] == 2 * 8192 * kernel_dim * 4
    assert set(meta["tiles"]) == {"fwd", "bwd"}
    report = render_report([{"kind": "meta", "name": "attn_impl", **meta}])
    assert (f"; the blocks keep {len(layers) * a_layer} bytes of the "
            "kernel's forward passes") in report
    assert report.rstrip().endswith(
        f"; one backward sweep of 5 products a tile, "
        f"{2 * 8192 * kernel_dim * 4} bytes of a key-value head's dk and dv "
        "standing in VMEM")
    # the line names the kernel's head size where it is not the model's
    assert ("fused at head size 128 (zero-padded), tiles" in report) == (
        kernel_dim != meta["head_dim"])
    gen["remat"] = "save_nothing"
    assert lm.attn_impl(gen, (batch, 8192))["kept_bytes"] == dict.fromkeys(
        names, 0)


def test_an_overfull_expert_buffer_fails_the_health_flag():
    """ISSUE 27 (d): 16 rows for some 140 held assignments: the step's
    finite flag fails, the update does not land, the monitor counts it."""
    cfg = tiny_cfg(expert_buffer_rows=16)
    cfg.diagnostics.on_nonfinite = "skip"
    _, _, train, buffers = seeded(cfg, 13)
    trainer, data = _trainer(cfg, train, buffers)
    trainer.diag._triaged = True   # no eager triage pass in a unit test
    before = jax.tree_util.tree_map(np.asarray,
                                    trainer.state["vars_G"]["params"])
    losses = trainer.gen_update(data)
    assert float(losses["moe/1/overflow"]) > 0
    assert not np.isfinite(float(losses["total"]))
    trainer.diag.drain(trainer)
    assert trainer.diag.nonfinite_events == 1
    after = trainer.state["vars_G"]["params"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        before, after)


def test_index_map_rule_engages_for_images_only():
    """ISSUE 27 (f): an image dataset's batch and a token batch through
    one `DevicePrefetcher` hook: the label's index map becomes the
    float32 stack, the tokens stay the int32 they were, and no expansion
    program is built for them."""
    from imaginaire_tpu.data import device_prefetch

    def on_device(batch):
        return device_prefetch.expand_index_labels(batch, 6)

    rng = np.random.RandomState(0)
    images = {"images": rng.rand(2, 8, 8, 3).astype(np.float32),
              "label": rng.randint(0, 5, (2, 8, 8)).astype(np.int32),
              "label_float": rng.rand(2, 8, 8, 1).astype(np.float32)}
    tokens = {"tokens": rng.randint(0, 256, (2, 64)).astype(np.int32)}
    feed = device_prefetch.DevicePrefetcher([images, tokens, tokens],
                                            on_device=on_device)
    first, second, third = list(feed)
    assert first["label"].shape == (2, 8, 8, 6)
    assert first["label"].dtype == jnp.float32
    assert "label_float" not in first
    programs = len(device_prefetch._EXPAND_PROGRAMS)
    for batch in (second, third):
        assert set(batch) == {"tokens"}
        assert batch["tokens"].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                      tokens["tokens"])
    assert len(device_prefetch._EXPAND_PROGRAMS) == programs


def test_packed_token_dataset_reads_its_shards(tmp_path):
    from imaginaire_tpu.data import get_train_and_val_dataloader

    cfg = tiny_cfg()
    train, _ = get_train_and_val_dataloader(cfg, seed=0)
    batch = next(iter(train))
    assert set(batch) == {"tokens"} and batch["tokens"].shape == (2, 64)
    assert batch["tokens"].dtype == np.int32
    assert getattr(train.dataset, "index_map_label", None) is None
    np.save(tmp_path / "bad.npy", np.zeros((4, 32), np.int32))
    cfg.data.train.roots = [str(tmp_path)]
    with pytest.raises(ValueError, match="seq_len"):
        get_train_and_val_dataloader(cfg, seed=0)
