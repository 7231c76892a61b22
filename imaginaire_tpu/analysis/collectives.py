"""Per-program collective accounting.

Two views, both recorded so a mesh change that doubles comm volume is a
diffable number in the ledger:

- ``jaxpr_collectives``: the collectives the program *explicitly* asks
  for (psum in a shard_map loss, all_gather in the sharded optimizer).
- ``hlo`` (from hlo_audit.collective_stats): what the SPMD partitioner
  actually emitted — includes resharding collectives invisible at the
  jaxpr level. This is the number that moves when the mesh changes.
"""

import numpy as np

from . import hlo_audit
from .jaxpr_audit import iter_eqns

# explicit collective primitives at the jaxpr level, as jax 0.9.0 names
# them (read off ``jax._src.lax.parallel``), each under the name the
# ledger reports it by. ``pmean`` traces to psum + div, ``pshuffle`` to
# ppermute and ``psum_scatter`` to reduce_scatter; inside a ``shard_map``
# with ``check_vma`` a psum of a varying value is ``psum_invariant``.
JAXPR_COLLECTIVE_PRIMS = {
    "psum": "psum", "psum_invariant": "psum", "unreduced_psum": "psum",
    "pmax": "pmax", "pmin": "pmin",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "all_gather_reduced": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "unreduced_reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all", "ragged_all_to_all": "all_to_all",
    "ppermute": "ppermute", "psend": "psend", "precv": "precv",
}


def _outvar_bytes(eqn):
    total = 0
    for var in eqn.outvars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for dim in shape:
            try:
                n *= int(dim)
            except (TypeError, ValueError):  # symbolic dims
                n = 0
                break
        total += n * np.dtype(dtype).itemsize
    return total


def jaxpr_collectives(closed_jaxpr):
    """prim -> {count, bytes} of explicit collective equations."""
    stats = {}
    for _, eqn in iter_eqns(closed_jaxpr):
        name = JAXPR_COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if name is not None:
            entry = stats.setdefault(name, {"count": 0, "bytes": 0})
            entry["count"] += 1
            entry["bytes"] += _outvar_bytes(eqn)
    return stats


def collective_summary(closed_jaxpr=None, hlo_text=None):
    """Combined accounting dict for the ledger entry. The headline
    ``op_count``/``bytes`` prefer the HLO view (post-partitioner truth)
    and fall back to the jaxpr view when no HLO text is available."""
    explicit = jaxpr_collectives(closed_jaxpr) if closed_jaxpr is not None \
        else {}
    summary = {"jaxpr": explicit}
    if hlo_text is not None:
        hlo = hlo_audit.collective_stats(hlo_text)
        summary["hlo"] = hlo
        summary["op_count"] = sum(v["count"] for v in hlo.values())
        summary["bytes"] = sum(v["bytes"] for v in hlo.values())
    else:
        summary["op_count"] = sum(v["count"] for v in explicit.values())
        summary["bytes"] = sum(v["bytes"] for v in explicit.values())
    return summary
