#!/usr/bin/env python
"""Component attribution for the SPADE bench number (VERDICT r2 #1/#4).

Times the full D/G training steps and their constituent programs on the
real chip at the zoo width (base128_bs4.yaml budget), writes PROFILE.md +
PROFILE.json at the repo root, and attempts a jax.profiler device trace
into logs/profile/.

Timing method: every measurement dispatches K sequential calls, waits
for the last output with ``jax.block_until_ready`` and takes the slope
between a small and a large K — the device queue serializes execution
while the constant host dispatch cost cancels in the difference (same
method as scripts/opsbench.py).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

REPEATS = 5
K_SMALL, K_LARGE = 2, 8


def _fence(out):
    jax.block_until_ready(out)


def measure(call, fence_from=None):
    """Per-call ms via the two-K slope. ``call()`` dispatches once and
    returns something device-resident; ``fence_from`` maps the last
    return value to the tree to fence on (default: the value itself)."""
    times = {}
    for k in (K_SMALL, K_LARGE):
        samples = []
        for _ in range(1 + REPEATS):  # first sample doubles as warmup
            t0 = time.perf_counter()
            out = None
            for _ in range(k):
                out = call()
            _fence(fence_from(out) if fence_from else out)
            samples.append((time.perf_counter() - t0) * 1e3)
        samples = samples[1:]
        times[k] = statistics.median(samples)
    return max(0.0, (times[K_LARGE] - times[K_SMALL]) / (K_LARGE - K_SMALL))


def main():
    import bench

    bs = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    trainer, label_ch = bench.build_zoo()
    data = jax.device_put(jax.tree_util.tree_map(
        np.asarray, bench.batch_of(bs, label_ch)))
    jax.block_until_ready(data)
    trainer.init_state(jax.random.PRNGKey(0), data)
    print(f"profiling zoo-width SPADE at bs={bs} on {jax.devices()[0]}",
          flush=True)

    rng = jax.random.PRNGKey(1)

    # --- component programs (jitted once each; executed after the full
    # steps so the optimizer/EMA arrays can be freed first) ---
    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def g_apply(vars_G, data, rng):
        out, _ = trainer._apply_G(vars_G, data, rng, training=True)
        return out["fake_images"]

    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def d_apply(vars_D, data, fake):
        # reduce over EVERY output so XLA can't dead-code-eliminate any
        # branch of the D graph (returning one sliced logit once made
        # this read as a 1ms "forward")
        out = trainer._apply_D(vars_D, data, {"fake_images": fake},
                               training=True)
        leaves = jax.tree_util.tree_leaves(
            (out["fake_outputs"], out["fake_features"]))
        return sum(jnp.sum(leaf.astype(jnp.float32)) for leaf in leaves)

    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def vgg_fwd(loss_params, fake, real):
        return trainer.perceptual(loss_params["perceptual"], fake,
                                  real.astype(fake.dtype))

    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def gen_loss_fwd(state, data):
        losses, _ = trainer.gen_forward(
            trainer._cast_net_vars(state["vars_G"]),
            trainer._cast_net_vars(state["vars_D"]),
            state["loss_params"], trainer._to_compute_dtype(data), rng)
        return trainer._total(
            {k: v.astype(jnp.float32) for k, v in losses.items()})

    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def gen_loss_grad(state, data):
        def loss_fn(params_G):
            vg = dict(state["vars_G"],
                      params=trainer._to_compute_dtype(params_G))
            losses, _ = trainer.gen_forward(
                vg, trainer._cast_net_vars(state["vars_D"]),
                state["loss_params"], trainer._to_compute_dtype(data), rng)
            return trainer._total(
                {k: v.astype(jnp.float32) for k, v in losses.items()})

        return jax.grad(loss_fn)(state["vars_G"]["params"])

    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def dis_loss_fwd(state, data):
        losses, _ = trainer.dis_forward(
            trainer._cast_net_vars(state["vars_G"]),
            trainer._cast_net_vars(state["vars_D"]),
            state["loss_params"], trainer._to_compute_dtype(data), rng)
        return losses["GAN"]

    results = {}

    def full_gen():
        trainer.gen_update(data)
        return trainer.state["vars_G"]["params"]

    def full_dis():
        trainer.dis_update(data)
        return trainer.state["vars_D"]["params"]

    full_cases = [
        ("dis_step_full", lambda: full_dis()),
        ("gen_step_full", lambda: full_gen()),
    ]

    def run_cases(cases):
        for name, call in cases:
            try:
                ms = measure(call)
            except Exception as e:  # noqa: BLE001 - HBM OOM etc.
                results[name] = None
                print(f"{name}: failed ({e!s:.80})", flush=True)
                continue
            results[name] = round(ms, 2)
            print(f"{name}: {ms:.2f} ms", flush=True)

    run_cases(full_cases)

    # --- attempt a real device trace around full steps (works only if
    # the platform exposes the profiler) ---
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "logs", "profile", "spade_zoo")
    try:
        jax.profiler.start_trace(trace_dir)
        trainer.dis_update(data)
        trainer.gen_update(data)
        _fence(trainer.state["vars_G"]["params"])
        jax.profiler.stop_trace()
        files = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                 for f in fs]
        size = sum(os.path.getsize(f) for f in files)
        trace_note = f"trace captured: {len(files)} files, {size // 1024} KB"
    except Exception as e:  # noqa: BLE001
        trace_note = f"device trace unavailable on this platform: {e!s:.120}"
    print(trace_note, flush=True)

    # Pure components don't need the optimizer/EMA arrays — drop them
    # from HBM so the un-donated grad program fits alongside.
    state = trainer.state
    slim = {"vars_G": state["vars_G"], "vars_D": state["vars_D"],
            "loss_params": state["loss_params"], "rng_G": state["rng_G"],
            "step": state["step"]}
    trainer.state = None
    state = None
    comp_data = trainer._to_compute_dtype(data)
    vars_G = trainer._cast_net_vars(slim["vars_G"])
    vars_D = trainer._cast_net_vars(slim["vars_D"])
    fake = g_apply(vars_G, comp_data, rng)

    run_cases([
        ("gen_loss_forward", lambda: gen_loss_fwd(slim, data)),
        ("gen_loss_grad", lambda: gen_loss_grad(slim, data)),
        ("dis_loss_forward", lambda: dis_loss_fwd(slim, data)),
        ("g_apply_forward", lambda: g_apply(vars_G, comp_data, rng)),
        ("d_apply_forward", lambda: d_apply(vars_D, comp_data, fake)),
        ("vgg19_perceptual_forward",
         lambda: vgg_fwd(slim["loss_params"], fake, comp_data["images"])),
    ])

    def diff(a, b):
        if results.get(a) is None or results.get(b) is None:
            return None
        return round(results[a] - results[b], 2)

    step = ((results.get("dis_step_full") or 0)
            + (results.get("gen_step_full") or 0))
    derived = {
        "gen_backward (grad - forward)":
            diff("gen_loss_grad", "gen_loss_forward"),
        "gen_optimizer+EMA+SN (step - grad)":
            diff("gen_step_full", "gen_loss_grad"),
        "dis_backward+opt (step - forward)":
            diff("dis_step_full", "dis_loss_forward"),
        "imgs_per_sec_implied": round(bs * 1e3 / step, 2) if step else None,
    }

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = {"batch_size": bs, "device": str(jax.devices()[0]),
               "components_ms": results, "derived_ms": derived,
               "trace": trace_note}
    with open(os.path.join(root, "PROFILE.json"), "w") as f:
        json.dump(payload, f, indent=1)

    lines = [
        "# SPADE zoo-width step attribution (real chip)",
        "",
        f"Config: `configs/projects/spade/cocostuff/base128_bs4.yaml` "
        f"verbatim (nf=128 G/D, kernel-5 separate-projection SPADE, "
        f"spectral norm, EMA, bf16), batch {bs}, device "
        f"`{jax.devices()[0]}`. Method: two-K dispatch-slope timing "
        f"(scripts/profile_bench.py); all numbers are per-call ms.",
        "",
        "| program | ms | % of D+G step |",
        "|---|---|---|",
    ]
    for name, ms in results.items():
        share = f"{100 * ms / step:.0f}%" if step and ms is not None else "-"
        note = ("" if name in ("dis_step_full", "gen_step_full")
                else " (overlaps the step programs above)")
        lines.append(f"| {name}{note} | {ms} | {share} |")
    lines += ["", "Derived:", ""]
    for k, v in derived.items():
        lines.append(f"- {k}: **{v}**")
    lines += ["", f"Profiler: {trace_note}", ""]
    with open(os.path.join(root, "PROFILE.md"), "w") as f:
        f.write("\n".join(lines))
    print("wrote PROFILE.md / PROFILE.json", flush=True)


if __name__ == "__main__":
    main()
