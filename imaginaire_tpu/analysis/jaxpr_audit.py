"""Closed-jaxpr static rules: the trace-level half of the graph audit.

Walks every equation of a ``ClosedJaxpr`` (recursing into the
sub-jaxprs carried by pjit/scan/while/cond/remat params) and flags the
statically-detectable failure classes that historically reached runtime:

- ``host_callback``   — io/debug/pure callbacks on the step path stall
                        the device pipeline on every dispatch
- ``f64_leak``        — a float64/complex128 equation output (TPUs
                        emulate f64 at ~1/10 throughput; on CPU tests
                        it silently doubles memory)
- ``island_cast``     — a ``convert_element_type`` down to bf16/f16
                        whose name stack lies inside a declared
                        ``fp32_island[...]`` scope (see islands.py)
- ``baked_constant``  — a closed-over constant above the byte threshold
                        baked into the executable (HBM waste that also
                        defeats donation)

Every violation names the offending jaxpr path
(``eqns[12]:pjit/body/eqns[3]:convert_element_type``) so the report is
actionable without re-deriving the trace.
"""

from dataclasses import dataclass

import numpy as np

from . import islands

LOW_PRECISION_DTYPES = ("bfloat16", "float16")
F64_DTYPES = ("float64", "complex128")
# flag each rule at most this many times per program; the count still
# lands in stats so nothing is hidden, the report just stays readable
MAX_PER_RULE = 16
DEFAULT_CONST_BYTES_LIMIT = 4 << 20  # 4 MiB

# host-callback primitives as jax 0.9.0 names them (jax.debug.print
# traces to ``debug_print``)
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "debug_print")


@dataclass
class Violation:
    rule: str
    program: str
    path: str
    message: str

    def as_dict(self):
        return {"rule": self.rule, "program": self.program,
                "path": self.path, "message": self.message}


def _is_jaxpr(obj):
    return hasattr(obj, "eqns") and hasattr(obj, "invars")


def _as_jaxpr(obj):
    """Accept Jaxpr or ClosedJaxpr (duck-typed: jax.core moved between
    versions)."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and _is_jaxpr(inner):
        return inner
    return obj if _is_jaxpr(obj) else None


def _sub_jaxprs(eqn):
    """(param_name, jaxpr) pairs nested inside one equation's params."""
    for key, value in eqn.params.items():
        candidates = value if isinstance(value, (list, tuple)) else (value,)
        for idx, item in enumerate(candidates):
            sub = _as_jaxpr(item)
            if sub is not None:
                name = key if len(candidates) == 1 else f"{key}[{idx}]"
                yield name, sub


def iter_eqns(jaxpr, path=""):
    """Depth-first (path, eqn) walk over a jaxpr and its sub-jaxprs."""
    jaxpr = _as_jaxpr(jaxpr)
    if jaxpr is None:
        return
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}eqns[{i}]:{eqn.primitive.name}"
        yield here, eqn
        for name, sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, path=f"{here}/{name}/")


def _var_dtype(var):
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return str(dtype) if dtype is not None else None


def _name_stack(eqn):
    try:
        return str(eqn.source_info.name_stack)
    except Exception:  # noqa: BLE001 — source info is best-effort
        return ""


def _const_bytes(const):
    nbytes = getattr(const, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    try:
        return int(np.asarray(const).nbytes)
    except Exception:  # noqa: BLE001
        return 0


def audit_jaxpr(program, closed_jaxpr, *,
                const_bytes_limit=DEFAULT_CONST_BYTES_LIMIT,
                check_f64=True):
    """Run every jaxpr-level rule. Returns (violations, stats) where
    stats = {eqns, f64_eqns, callback_eqns, island_casts, const_bytes}.
    """
    violations = []
    per_rule = {}
    stats = {"eqns": 0, "f64_eqns": 0, "callback_eqns": 0,
             "island_casts": 0, "const_bytes": 0}

    def add(rule, path, message):
        per_rule[rule] = per_rule.get(rule, 0) + 1
        if per_rule[rule] <= MAX_PER_RULE:
            violations.append(Violation(rule, program, path, message))

    jaxpr = _as_jaxpr(closed_jaxpr)
    consts = list(getattr(closed_jaxpr, "consts", ()) or ())
    constvars = list(getattr(jaxpr, "constvars", ()) or ())
    for i, const in enumerate(consts):
        nbytes = _const_bytes(const)
        stats["const_bytes"] += nbytes
        if const_bytes_limit and nbytes > const_bytes_limit:
            shape = tuple(getattr(const, "shape", ()) or ())
            dtype = str(getattr(const, "dtype", type(const).__name__))
            name = constvars[i] if i < len(constvars) else i
            add("baked_constant", f"constvars[{i}]",
                f"closed-over constant {name} ({dtype}{list(shape)}, "
                f"{nbytes} bytes) baked into the executable "
                f"(limit {const_bytes_limit}); pass it as an argument "
                f"or fold it into state")

    for path, eqn in iter_eqns(jaxpr):
        stats["eqns"] += 1
        prim = eqn.primitive.name
        if prim in _CALLBACK_PRIMS:
            stats["callback_eqns"] += 1
            stack = _name_stack(eqn)
            add("host_callback", path,
                f"host callback primitive '{prim}' on the compiled path"
                + (f" (scope {stack})" if stack else "")
                + "; each dispatch round-trips to the host")
        if check_f64:
            for j, outvar in enumerate(eqn.outvars):
                dtype = _var_dtype(outvar)
                if dtype in F64_DTYPES:
                    stats["f64_eqns"] += 1
                    add("f64_leak", path,
                        f"'{prim}' produces {dtype} (outvar {j}); "
                        f"double precision leaked into the program")
        if prim == "convert_element_type":
            new_dtype = str(eqn.params.get("new_dtype", ""))
            if new_dtype in LOW_PRECISION_DTYPES:
                island = islands.island_of(_name_stack(eqn))
                if island is not None:
                    stats["island_casts"] += 1
                    add("island_cast", path,
                        f"cast to {new_dtype} inside "
                        f"fp32_island[{island}]; keep the island in "
                        f"fp32 and cast back to the compute dtype "
                        f"outside the scope")

    for rule, count in per_rule.items():
        if count > MAX_PER_RULE:
            violations.append(Violation(
                rule, program, "...",
                f"{count - MAX_PER_RULE} further {rule} violations "
                f"truncated (total {count})"))
    return violations, stats
