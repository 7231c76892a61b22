"""Remat x dtype x batch-size memory autotuner (ISSUE 10).

Enumerates (remat policy, compute dtype, batch size) candidates per
(family, resolution), AOT-compiles each one's step programs through the
compile ledger on sharded ``ShapeDtypeStruct`` trees — candidates are
NEVER executed, so shapes that do not fit a real chip still report
``memory_analysis`` on the virtual CPU mesh — and reduces the
measurements to a pareto frontier over (XLA temp bytes, step flops).
The winner under ``--mem-budget-frac`` becomes the config default
(spade-512 and 512x1024 vid2vid ship the autotuned policy).

The pure half of this file (candidate enumeration, pareto filtering,
budget recommendation) has no jax dependency beyond the policy-name
registry and is unit-tested against a fake ledger
(tests/test_memory_autotune.py); the AOT driver below it follows
scripts/partition_budget.py.

Usage (fresh process; the virtual mesh must be set before jax wakes up):
  python scripts/memory_autotune.py --families spade --hw 512 512 \
      --bs 4 --json /tmp/membench.json
  python scripts/memory_autotune.py --families vid2vid --hw 512 1024 \
      --bs 1 --policies none,blocks --dtypes float32,bfloat16
  python scripts/memory_autotune.py \
      --families spade,pix2pixHD,unit,munit,funit
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DTYPES = ("float32", "bfloat16")


class MemoryBudgetError(RuntimeError):
    """No candidate's AOT footprint fits the memory budget."""


# --------------------------------------------------------------- pure core


MODULATIONS = ("fused", "unfused")


def enumerate_candidates(policies, dtypes, batch_sizes, modulations=None):
    """The candidate grid, validated: every policy name must resolve in
    the shared registry (one error message, one registry — the same
    resolver the generators use) and every dtype must be a known
    compute dtype.

    ``modulations`` (ISSUE 16) adds the fused-SPADE-epilogue axis:
    'fused' routes the generator's SPADE epilogues through
    ``ops.spade_modulation`` ('fused' implementation), 'unfused' pins
    the reference composition ('none'). When None (the default) the
    axis is absent and candidate names keep their PR-9 shape."""
    from imaginaire_tpu.optim.remat import resolve_policy

    for mod in modulations or ():
        if mod not in MODULATIONS:
            raise ValueError(
                f"memory_autotune --modulations={mod!r} is not a known "
                f"modulation mode; use one of " + ", ".join(MODULATIONS))
    out = []
    for policy in policies:
        resolve_policy(policy, where="memory_autotune --policies")
        for dtype in dtypes:
            if dtype not in DTYPES:
                raise ValueError(
                    f"memory_autotune --dtypes={dtype!r} is not a known "
                    f"compute dtype; use one of " + ", ".join(DTYPES))
            for bs in batch_sizes:
                if int(bs) < 1:
                    raise ValueError(f"batch size must be >= 1, got {bs}")
                for mod in (modulations or (None,)):
                    cand = {
                        "name": f"{policy}/{dtype}/bs{int(bs)}",
                        "remat_policy": policy,
                        "compute_dtype": dtype,
                        "batch_size": int(bs),
                    }
                    if mod is not None:
                        cand["name"] += f"/{mod}"
                        cand["spade_modulation"] = mod
                    out.append(cand)
    return out


def _measured(rows):
    """Rows eligible for pareto/recommendation: compiled cleanly AND
    were not legalized away from the requested dtype (ISSUE 16: CPU
    lowers bf16 convs through f32, inflating temp by ~24% — those rows
    are recorded for the table but must not compete as candidates)."""
    return [r for r in rows
            if r.get("temp_bytes") is not None
            and r.get("flops") is not None
            and not r.get("legalized")]


def pareto_frontier(rows):
    """Non-dominated rows minimizing (temp_bytes, flops). A row is
    dominated when another measured row is <= on both axes and < on at
    least one. Ties on both axes keep every tied row (the recommender
    breaks them). Unmeasured rows (failed compiles) never make the
    frontier."""
    measured = _measured(rows)
    front = []
    for r in measured:
        dominated = any(
            o is not r
            and o["temp_bytes"] <= r["temp_bytes"]
            and o["flops"] <= r["flops"]
            and (o["temp_bytes"] < r["temp_bytes"]
                 or o["flops"] < r["flops"])
            for o in measured)
        if not dominated:
            front.append(r)
    return sorted(front, key=lambda r: (r["temp_bytes"], r["flops"],
                                        r["name"]))


def recommend(rows, bytes_limit=None, mem_budget_frac=0.9):
    """The winning candidate under the budget: among measured rows whose
    ``footprint_bytes`` (worst executable total + train state) fits
    ``mem_budget_frac * bytes_limit``, prefer the LARGEST batch size —
    the whole point of spending less on activations is cashing it in as
    batch — then the smallest temp bytes, then the fewest flops, then
    name order for determinism. With no ``bytes_limit`` (CPU backend)
    every measured row is feasible. Raises MemoryBudgetError when
    nothing fits: an autotuner silently recommending an OOM is worse
    than one refusing."""
    measured = _measured(rows)
    if not measured:
        raise MemoryBudgetError("no candidate produced a measurement")
    if bytes_limit:
        budget = float(mem_budget_frac) * float(bytes_limit)
        feasible = [r for r in measured
                    if r.get("footprint_bytes") is not None
                    and r["footprint_bytes"] <= budget]
        if not feasible:
            tightest = min(r.get("footprint_bytes", math.inf)
                           for r in measured)
            raise MemoryBudgetError(
                f"no candidate fits mem_budget_frac={mem_budget_frac:g} "
                f"of bytes_limit={int(bytes_limit)} "
                f"(budget {int(budget)} bytes; smallest candidate "
                f"footprint {int(tightest)} bytes)")
    else:
        feasible = measured
    return min(feasible, key=lambda r: (-r["batch_size"], r["temp_bytes"],
                                        r["flops"], r["name"]))


def profile_rows(family, hw, rows, frontier_names, recommended_name):
    """Markdown table lines for one family sweep."""
    lines = []
    for r in sorted(rows, key=lambda r: r["name"]):
        if r.get("temp_bytes") is None:
            continue
        marks = []
        if r.get("legalized"):
            marks.append("legalized")
        if r["name"] in frontier_names:
            marks.append("pareto")
        if r["name"] == recommended_name:
            marks.append("**winner**")
        lines.append(
            f"| {family} {hw[0]}x{hw[1]} | {r['remat_policy']} "
            f"| {r['compute_dtype']} | {r['batch_size']} "
            f"| {_gib(r['temp_bytes'])} | {r['flops']:.2e} "
            f"| {', '.join(marks) or '-'} |")
    return lines


def _gib(n):
    return f"{n / 2**30:.2f} GiB"


def row_from_ledger(cand, family, hw, executables, flops_by_label,
                    state_bytes):
    """Reduce per-executable ledger memory dicts + flops into one
    measurement row: temp_bytes is the WORST executable's temp
    allocation (programs run one at a time; their temps don't add),
    flops is the step total (dis + gen both run every iteration), and
    footprint is worst executable total + resident train state."""
    row = dict(cand, family=family, hw=list(hw),
               executables=dict(executables),
               temp_bytes=None, flops=None, state_bytes=int(state_bytes),
               footprint_bytes=None, error=None)
    worst_total = 0
    for label, mem in executables.items():
        if not mem:
            row["error"] = f"lower/compile of {label} failed"
            row["temp_bytes"] = row["flops"] = None
            return row
        flops = flops_by_label.get(label)
        if flops is not None:
            row["flops"] = (row["flops"] or 0.0) + float(flops)
        if mem.get("temp_bytes") is not None:
            row["temp_bytes"] = max(int(mem["temp_bytes"]),
                                    row["temp_bytes"] or 0)
        worst_total = max(worst_total, int(mem.get("total_bytes", 0) or 0))
    row["footprint_bytes"] = worst_total + row["state_bytes"]
    return row


# --------------------------------------------------------------- AOT driver


def _force_virtual_mesh(n):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _repo_config(*parts):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from imaginaire_tpu.config import Config

    cfg = Config(os.path.join(here, "configs", "projects", *parts))
    if "perceptual_loss" in cfg.trainer:
        cfg.trainer.perceptual_loss.allow_random_init = True
        cfg.trainer.perceptual_loss.pop("weights_path", None)
    return cfg


def _image_sds(bs, h, w, c):
    import jax
    import numpy as np

    return jax.ShapeDtypeStruct((bs, h, w, c), np.float32)


def _spade_family(hw, bs):
    cfg = _repo_config("spade", "cocostuff", "base128_bs4.yaml")
    cfg.data.train.batch_size = bs

    def batch(n_lab):
        h, w = hw
        return {"images": _image_sds(bs, h, w, 3),
                "label": _image_sds(bs, h, w, n_lab)}

    return cfg, batch, None


def _pix2pixHD_family(hw, bs):
    import jax
    import numpy as np

    cfg = _repo_config("pix2pixHD", "cityscapes", "bf16.yaml")
    cfg.data.train.batch_size = bs

    def batch(n_lab):
        h, w = hw
        # post-preprocessing schema: seg channels + binary edge map in
        # label, raw instance ids alongside (trainers/pix2pixHD.py)
        return {"images": _image_sds(bs, h, w, 3),
                "label": _image_sds(bs, h, w, n_lab - 1),
                "instance_maps": jax.ShapeDtypeStruct((bs, h, w, 1),
                                                      np.int32)}

    return cfg, batch, None


def _vid2vid_family(hw, bs, frames=3):
    cfg = _repo_config("vid2vid", "cityscapes", "bf16.yaml")
    if "flow_network" in cfg:
        # frozen teacher weights don't resolve here; the warp-consistency
        # fallback keeps the G/D step structure identical
        cfg.pop("flow_network")
    cfg.data.train.batch_size = bs

    def init_batch(n_lab):
        import jax
        import numpy as np

        h, w = hw
        return {"images": jax.ShapeDtypeStruct((bs, frames, h, w, 3),
                                               np.float32),
                "label": jax.ShapeDtypeStruct((bs, frames, h, w, n_lab),
                                              np.float32)}

    def step_batch(n_lab):
        # the per-frame programs consume data_t (the t=0 frame: full
        # G fwd+bwd+opt without prev-frame inputs)
        h, w = hw
        return {"image": _image_sds(bs, h, w, 3),
                "label": _image_sds(bs, h, w, n_lab)}

    return cfg, init_batch, step_batch


def _unit_family(hw, bs):
    cfg = _repo_config("unit", "winter2summer", "base48_bs1.yaml")
    cfg.data.train.batch_size = bs

    def batch(_n_lab):
        h, w = hw
        return {"images_a": _image_sds(bs, h, w, 3),
                "images_b": _image_sds(bs, h, w, 3)}

    return cfg, batch, None


def _munit_family(hw, bs):
    cfg = _repo_config("munit", "summer2winter_hd", "bf16.yaml")
    cfg.data.train.batch_size = bs

    def batch(_n_lab):
        h, w = hw
        return {"images_a": _image_sds(bs, h, w, 3),
                "images_b": _image_sds(bs, h, w, 3)}

    return cfg, batch, None


def _funit_family(hw, bs):
    import jax
    import numpy as np

    cfg = _repo_config("funit", "animal_faces", "base64_bs8_class119.yaml")
    cfg.data.train.batch_size = bs

    def batch(_n_lab):
        h, w = hw
        return {"images_content": _image_sds(bs, h, w, 3),
                "images_style": _image_sds(bs, h, w, 3),
                "labels_content": jax.ShapeDtypeStruct((bs,), np.int32),
                "labels_style": jax.ShapeDtypeStruct((bs,), np.int32)}

    return cfg, batch, None


FAMILIES = {
    # family -> (builder, default hw, default bs)
    "spade": (_spade_family, (512, 512), 4),
    "vid2vid": (_vid2vid_family, (512, 1024), 1),
    "pix2pixHD": (_pix2pixHD_family, (256, 512), 2),
    "unit": (_unit_family, (256, 256), 1),
    "munit": (_munit_family, (256, 256), 1),
    "funit": (_funit_family, (128, 128), 2),
}


def _apply_candidate(cfg, cand):
    """Inject one candidate's knobs into a family config: the shared
    per-block remat policy on BOTH nets, the end-to-end precision
    policy (mixed_precision wins over the legacy scalar in
    BaseTrainer.__init__; both are set so either resolution path
    agrees), and — when the candidate carries the ISSUE-16 modulation
    axis — the fused-SPADE-epilogue knob. The fused op implements
    instance-norm statistics only, so the axis also pins the SPADE base
    norm to 'instance' on BOTH arms (fused AND unfused) to keep the
    comparison apples-to-apples; rows from such sweeps are therefore
    not directly comparable to sync_batch-base rows."""
    from imaginaire_tpu.config import cfg_get

    cfg.gen.remat = cand["remat_policy"]
    cfg.dis.remat = cand["remat_policy"]
    cfg.trainer.compute_dtype = cand["compute_dtype"]
    cfg.trainer.mixed_precision = {
        "enabled": cand["compute_dtype"] != "float32",
        "compute_dtype": cand["compute_dtype"],
    }
    mod = cand.get("spade_modulation")
    if mod:
        anp = dict(cfg_get(cfg.gen, "activation_norm_params", None) or {})
        anp["activation_norm_type"] = "instance"
        anp["fused_modulation"] = "fused" if mod == "fused" else "none"
        cfg.gen.activation_norm_params = anp
    return cfg


def _tree_bytes(shapes):
    import jax

    return sum(int(math.prod(s.shape)) * int(s.dtype.itemsize)
               for s in jax.tree_util.tree_leaves(shapes))


def measure_candidate(family, hw, cand, mesh):
    """AOT-compile one candidate's step programs (never executed) and
    return its measurement row. A failed lower/compile reports the
    error and leaves temp_bytes/flops None — the pure core skips it."""
    import jax
    import numpy as np

    from imaginaire_tpu.parallel.sharding import batch_pytree_shardings
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.telemetry import xla_obs
    from imaginaire_tpu.utils.data import (
        get_paired_input_label_channel_number,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    builder, _, _ = FAMILIES[family]
    cfg, init_batch_fn, step_batch_fn = builder(hw, cand["batch_size"])
    _apply_candidate(cfg, cand)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    try:
        n_lab = get_paired_input_label_channel_number(cfg.data)
    except Exception:  # noqa: BLE001 — unpaired families have no labels
        n_lab = 0

    init_batch = init_batch_fn(n_lab)
    zeros = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), init_batch)
    state_shapes = jax.eval_shape(
        lambda key, b: trainer.init_state(key, b),
        jax.ShapeDtypeStruct((2,), np.uint32), zeros)
    trainer.state = None  # eval_shape left SDS in self.state

    state_sds = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
        state_shapes)
    step_batch = (step_batch_fn or init_batch_fn)(n_lab)
    batch_sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        step_batch, batch_pytree_shardings(step_batch, mesh))

    if family == "vid2vid":
        programs = {"vid_dis_step": trainer._jit_vid_dis,
                    "vid_gen_step": trainer._jit_vid_gen}
    else:
        programs = {"dis_step": trainer._jit_dis_step,
                    "gen_step": trainer._jit_gen_step}

    executables = {}
    for label, prog in programs.items():
        print(f"# AOT {family} {cand['name']}: compiling {label} ...",
              flush=True)
        executables[label] = prog.aot_compile(state_sds, batch_sds)
    row = row_from_ledger(cand, family, hw, executables,
                          xla_obs.ledger_flops(),
                          _tree_bytes(state_shapes))
    if cand["compute_dtype"] != "float32" and jax.default_backend() != "tpu":
        # the CPU backend legalizes bf16 convs through f32 (about +24%
        # temp in an earlier CPU sweep): record the row but bar it from
        # pareto/recommendation (ISSUE 16)
        row["legalized"] = True
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="AOT remat x dtype x batch-size memory autotuner")
    ap.add_argument("--families", default="spade",
                    help="comma list of " + ",".join(FAMILIES))
    ap.add_argument("--hw", type=int, nargs=2, default=None,
                    help="override the family default resolution "
                         "(single-family runs only)")
    ap.add_argument("--bs", default=None,
                    help="comma list of batch sizes (default: the "
                         "family default)")
    ap.add_argument("--policies",
                    default="none,blocks,dots_saveable,save_nothing")
    ap.add_argument("--dtypes", default="float32,bfloat16")
    ap.add_argument("--modulations", default=None,
                    help="comma list from " + ",".join(MODULATIONS)
                         + " — adds the fused-SPADE-epilogue axis "
                           "(ISSUE 16); omitted by default")
    ap.add_argument("--mem-budget-frac", type=float, default=0.9)
    ap.add_argument("--devices", type=int, default=1,
                    help="virtual CPU mesh size (data axis)")
    ap.add_argument("--json", default=None,
                    help="write the machine-readable report here")
    args = ap.parse_args(argv)
    families = [f.strip() for f in args.families.split(",") if f.strip()]
    unknown = [f for f in families if f not in FAMILIES]
    if unknown:
        ap.error(f"unknown families {unknown}; choose from "
                 + ",".join(FAMILIES))
    if args.hw and len(families) > 1:
        ap.error("--hw applies to single-family runs only")
    _force_virtual_mesh(args.devices)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from imaginaire_tpu.utils import compile_cache

    compile_cache.configure()
    import numpy as np

    from imaginaire_tpu.parallel.mesh import create_mesh, set_mesh
    from imaginaire_tpu.telemetry import xla_obs

    n_dev = max(args.devices, 1)
    mesh = create_mesh(("data", "model"), (n_dev, 1),
                       devices=np.array(jax.devices()[:n_dev]))
    set_mesh(mesh)
    bytes_limit = None
    stats = xla_obs.device_memory_stats()
    limits = [s.get("bytes_limit") for s in stats.values()
              if s.get("bytes_limit")]
    if limits:
        bytes_limit = int(min(limits))

    policies = [p.strip() for p in args.policies.split(",") if p.strip()]
    dtypes = [d.strip() for d in args.dtypes.split(",") if d.strip()]
    modulations = ([m.strip() for m in args.modulations.split(",")
                    if m.strip()] if args.modulations else None)
    # re-sweeping one family must not drop the others' rows: start from
    # the existing report and update the swept families in place
    report = {"families": {}}
    if args.json and os.path.exists(args.json):
        with open(args.json) as f:
            report = json.load(f)
        report.setdefault("families", {})
    report.update(mem_budget_frac=args.mem_budget_frac,
                  bytes_limit=bytes_limit, devices=n_dev)
    md = ["| family | remat | dtype | bs | temp | flops | verdict |",
          "|---|---|---|---|---|---|---|"]
    for family in families:
        _, default_hw, default_bs = FAMILIES[family]
        hw = tuple(args.hw) if args.hw else default_hw
        batch_sizes = ([int(b) for b in args.bs.split(",")]
                       if args.bs else [default_bs])
        cands = enumerate_candidates(policies, dtypes, batch_sizes,
                                     modulations=modulations)
        rows = [measure_candidate(family, hw, c, mesh) for c in cands]
        # union with the family's prior rows at the same resolution
        # (same-name rows refresh in place) so a narrow re-sweep — e.g.
        # the ISSUE-16 modulation axis — extends the table instead of
        # discarding the PR-9 sweep
        prior_family = report["families"].get(family) or {}
        if list(prior_family.get("hw", ())) == list(hw):
            by_name = {r["name"]: r for r in prior_family.get("rows", ())}
            by_name.update({r["name"]: r for r in rows})
            rows = list(by_name.values())
        front = pareto_frontier(rows)
        front_names = [r["name"] for r in front]
        try:
            winner = recommend(rows, bytes_limit=bytes_limit,
                               mem_budget_frac=args.mem_budget_frac)
            winner_name, refusal = winner["name"], None
        except MemoryBudgetError as e:
            winner_name, refusal = None, str(e)
            print(f"# {family}: REFUSED — {e}", flush=True)
        report["families"][family] = {
            "hw": list(hw),
            "rows": rows,
            "pareto": front_names,
            "recommended": winner_name,
            "refusal": refusal,
        }
        md.extend(profile_rows(family, hw, rows, front_names,
                               winner_name))
    print("\n".join(md))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=str)
            f.write("\n")
        print(f"# wrote {args.json}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
