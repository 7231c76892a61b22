"""TPU-native equivalents of the reference's CUDA extensions.

Reference ops (ref: imaginaire/third_party/):
  resample2d       — flow-based backward warping (resample2d_kernel.cu)
  channelnorm      — per-pixel L-p norm across channels (channelnorm_kernel.cu)
  correlation      — FlowNetC cost volume (correlation_cuda_kernel.cu)
  spade_modulation — fused SPADE norm->modulate epilogue (ISSUE 16; the
                     reference composes this from stock ops, but the
                     synthesis hot path's ``norm(x) * (1 + Σγ) + Σβ``
                     materializes three full-size tensors the fused op
                     keeps out of HBM)

canonical imports
-----------------
``from imaginaire_tpu.ops import resample2d`` binds the FUNCTION — and
because the package also has a ``resample2d`` submodule, that name
shadows the module everywhere (``import imaginaire_tpu.ops.resample2d``
followed by ``imaginaire_tpu.ops.resample2d.AUTO_IMPLEMENTATION`` dies
with "'function' object has no attribute ...": the package attribute
won the race; this bit the memory autotuner once already). The rules:

  - calling the op:      ``from imaginaire_tpu.ops import resample2d``
  - module attributes:   ``from imaginaire_tpu.ops import resample2d_mod``
    (every op exports an explicit ``<op>_mod`` alias; reach constants as
    ``resample2d_mod.AUTO_IMPLEMENTATION``)
  - NEVER ``import imaginaire_tpu.ops.resample2d`` and then dot through
    ``imaginaire_tpu.ops.resample2d`` — you get the function.

Each op has a pure-jnp implementation (differentiable; XLA autodiff turns
the gather-style forward into the scatter-add backward the CUDA code does
with atomicAdd). ``implementation='auto'`` resolves to each module's
``AUTO_IMPLEMENTATION``: resample2d to the jnp/XLA path, correlation to
the 'mxu' formulation — the cost volume recast as per-displacement-row
matmuls plus a strided band-gather — with the scan path covering general
kernel sizes, spade_modulation to 'fused' (the custom_vjp
residual-trimming path). channelnorm has its jnp path and no
``implementation``.

attention
---------
``ops/attention.py`` (the token model's causal grouped-query attention)
is not a reference op and takes no ``implementation``: it picks its own
arm from the backend, the head size and the length (``arm_of``), the
fused Pallas kernel of ``ops/pallas/causal_attention_kernel.py`` or
query blocks in plain ``jax.numpy``. Import it as a module,
``from imaginaire_tpu.ops import attention``.

delta_rule
----------
``ops/delta_rule.py`` (the token model's gated delta rule, Kimi Delta
Attention's chunked WY form) is not a reference op either and takes no
``implementation``: it picks its own arm from the backend, the head size,
the chunk and the length (``arm_of``), the two Pallas sweeps of
``ops/pallas/delta_rule_kernel.py`` or ``kda_scan`` in plain
``jax.numpy``. Import it as a module,
``from imaginaire_tpu.ops import delta_rule``.

state_space
-----------
``ops/state_space.py`` (the token model's Mamba-2 recurrence in its
chunked state-space dual form, ``ssd_scan``) is not a reference op either
and takes no ``implementation``: it picks its own arm from the backend,
the head size, the state, the chunk, the length and the heads a group
(``arm_of``), the two Pallas sweeps of
``ops/pallas/state_space_kernel.py`` or ``ssd_chunks`` in plain
``jax.numpy``. Import it as a module,
``from imaginaire_tpu.ops import state_space``.

held_experts, grouped_matmul
----------------------------
The token model's other blocks of numerics, modules likewise and without
an ``implementation``: ``ops/held_experts.py`` (an
expert layer's held share: the sort into the buffer, the tiers of its
filled prefix, the rows moved by segments, the backward pass written
out) and ``ops/grouped_matmul.py`` (the experts' grouped products: the
Pallas kernels of ``ops/pallas/grouped_matmul_kernel.py`` or
``lax.ragged_dot``, by ``arm_of``). They take arrays, shapes and sizes;
nothing under ``ops/`` imports a model or a trainer
(``tests/test_ops_imports.py``).

auto pins
---------
Every ``AUTO_IMPLEMENTATION`` is pinned to the XLA formulation; not
measured on this installation.
"""

# module aliases FIRST (while the package attributes still point at the
# submodules), then the function imports that shadow them
from imaginaire_tpu.ops import resample2d as resample2d_mod
from imaginaire_tpu.ops import channelnorm as channelnorm_mod
from imaginaire_tpu.ops import correlation as correlation_mod
from imaginaire_tpu.ops import spade_modulation as spade_modulation_mod
from imaginaire_tpu.ops.resample2d import resample2d
from imaginaire_tpu.ops.channelnorm import channelnorm
from imaginaire_tpu.ops.correlation import correlation
from imaginaire_tpu.ops.spade_modulation import spade_modulation

OP_MODULES = {
    "resample2d": resample2d_mod,
    "channelnorm": channelnorm_mod,
    "correlation": correlation_mod,
    "spade_modulation": spade_modulation_mod,
}


def resolved_implementations():
    """{op: implementation} each op's ``implementation='auto'`` resolves
    to — the single source is each module's ``AUTO_IMPLEMENTATION``
    constant."""
    return {op: mod.AUTO_IMPLEMENTATION for op, mod in OP_MODULES.items()}


__all__ = ["resample2d", "channelnorm", "correlation", "spade_modulation",
           "resample2d_mod", "channelnorm_mod", "correlation_mod",
           "spade_modulation_mod", "OP_MODULES",
           "resolved_implementations"]
