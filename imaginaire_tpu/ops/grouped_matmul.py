"""The grouped product of an expert layer: the rows of ``lhs`` lie sorted
by group, ``group_sizes[g]`` of them belong to group ``g``, and row ``r``
is multiplied by its group's matrix, ``out[r] = lhs[r] @ rhs[group of r]``.

One entry point, two arms, and ``grouped_matmul`` picks between them from
what it can observe (``arm_of``), with no option:

  ``kernel``      this repo's Pallas TPU kernels (``ops/pallas/
                  grouped_matmul_kernel.py``): tiles picked from the
                  widths they are given (``tiles_of``), a ragged last tile
                  masked in VMEM, and a grid that visits only the row
                  tiles that hold a group's rows, so the time follows the
                  filled rows and not the buffer. Where the backend is a
                  TPU, the row tile divides the rows, both widths fill a
                  lane tile and neither is wider than the widest
                  contraction measured (each is contracted whole in one
                  of the passes).
  ``ragged_dot``  ``lax.ragged_dot``, the compiler's own kernel on a TPU
                  and plain XLA elsewhere: the CPU, where the tests run,
                  and the unit-test widths.

Both take the compute dtype's operands, accumulate in float32 and return
the compute dtype, forward and in both gradients. Neither writes the rows
past the last group, forward or in the gradient to the rows: the caller
masks them (``ops/held_experts.py``'s ``held_experts_part``). The
kernel's tiles were chosen on a v5e chip by
``scripts/sweep_grouped_products.py`` (PERF.md, PR 38); they are not
configuration.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
from jax import lax

from imaginaire_tpu.ops.pallas import grouped_matmul_kernel as kernel

# the rows of a tile, which have to divide the rows the kernel is given:
# the smaller the tile, the fewer rows past a group's end a visit computes
# (a layer holds 200 to 500 rows a group), and at 128 the matrix unit is
# as busy as at 512 (PERF.md, PR 38)
ROW_TILE = 128
# the widest tile of the product's width (of the weights' gradient's last
# axis), and the widest contraction the kernel takes: it stands whole in
# VMEM, and 4096 is the widest measured
WIDEST_TILE = 1024
WIDEST_CONTRACTION = 4096


class Tiles(NamedTuple):
    """(rows, width) of the output's tile in the two passes of
    ``kernel.rows``; the gradient to the weights contracts ``fwd``'s rows
    a visit into ``fwd``'s width of the weights' gradient. The
    contraction stands whole in every pass."""
    fwd: tuple
    dlhs: tuple


def width_tile(width):
    """The tile of a product's width: of the multiples of 128 lanes up to
    ``WIDEST_TILE``, the one whose tiles reach least past the width's
    edge, and of those the widest (1856: 640, three tiles to 1920; 2688:
    896; 1280: 640; 2048 and 4096: 1024). On the chip the time followed
    the columns computed, and of two tilings that compute the same, the
    one with fewer, wider tiles fetched the rows less often."""
    lanes = kernel.LANES
    return min(range(lanes, WIDEST_TILE + 1, lanes),
               key=lambda tile: (-(-width // tile) * tile, -tile))


def tiles_of(contracted, width):
    """The kernel's tiles for ``lhs`` (rows, contracted) by ``rhs``
    (groups, contracted, width)."""
    return Tiles(fwd=(ROW_TILE, width_tile(width)),
                 dlhs=(ROW_TILE, width_tile(contracted)))


# One program a call site would lower one Mosaic kernel each, a quarter of
# a second of every process's set-up apiece, compile cache or not (64 in
# Nemotron's step: four layers, two tiers, eight calls). Under ``jax.jit``
# the calls of one shape share one lowered function, which the compiler
# inlines at each site under that site's ``op_name``.
# lint: allow(bare-jit) -- inlined into the step program, never dispatched
_rows = jax.jit(kernel.rows, static_argnames=(
    "tile", "transposed", "name", "interpret"))
# lint: allow(bare-jit) -- inlined into the step program, never dispatched
_weights = jax.jit(kernel.weights, static_argnames=(
    "tile", "name", "interpret"))


def arm_of(rows, contracted, width):
    """``"kernel"`` or ``"ragged_dot"``: which arm ``grouped_matmul``
    takes for ``lhs`` (rows, contracted) by ``rhs`` (groups, contracted,
    width) on this process's backend."""
    on_tpu = jax.default_backend() == "tpu"
    fits = (rows % ROW_TILE == 0
            and kernel.LANES <= min(contracted, width)
            and max(contracted, width) <= WIDEST_CONTRACTION)
    return "kernel" if on_tpu and fits else "ragged_dot"


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (rows, contracted), ``rhs`` (groups, contracted, width),
    ``group_sizes`` (groups,) int32 to (rows, width) in ``lhs``'s dtype."""
    if arm_of(lhs.shape[0], *rhs.shape[1:]) == "kernel":
        return kernel_grouped_matmul(lhs, rhs, group_sizes)
    return lax.ragged_dot(lhs, rhs, group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def kernel_grouped_matmul(lhs, rhs, group_sizes, tiles=None,
                          interpret=False):
    """``grouped_matmul`` by the kernels. ``tiles`` default to
    ``tiles_of`` the shapes; ``interpret`` runs the kernels in Pallas's
    interpreter (the CPU tests)."""
    return _kernel_fwd(lhs, rhs, group_sizes, tiles, interpret)[0]


def _kernel_fwd(lhs, rhs, group_sizes, tiles, interpret):
    tiles = tiles or tiles_of(*rhs.shape[1:])
    out = _rows(lhs, rhs, group_sizes, tile=tiles.fwd,
                name="grouped_rows_fwd", interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _kernel_bwd(tiles, interpret, saved, dout):
    lhs, rhs, group_sizes = saved
    tiles = tiles or tiles_of(*rhs.shape[1:])
    dlhs = _rows(dout, rhs, group_sizes, tile=tiles.dlhs, transposed=True,
                 name="grouped_rows_dlhs", interpret=interpret)
    drhs = _weights(lhs, dout, group_sizes, tile=tiles.fwd,
                    name="grouped_weights_drhs", interpret=interpret)
    return dlhs, drhs, None


kernel_grouped_matmul.defvjp(_kernel_fwd, _kernel_bwd)
