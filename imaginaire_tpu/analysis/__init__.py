"""Static analysis plane over every compiled program (ISSUE 12).

Two layers:

- **Graph audit** (jaxpr_audit / hlo_audit / donation / collectives):
  runs automatically on every ``xla_obs.compiled_program`` compile —
  the ledger entry gains an ``audit`` dict and the
  ``xla/graph/<label>/*`` counters feed the report and
  ``check_run_health --max-graph-violations``.
- **Source lint** (ast_rules + ``scripts/lint_graph.py``): repo-wide
  AST rules with an explicit inline-allowlist syntax.

``audit_program`` below is the orchestrator xla_obs calls with
whatever artifacts the compile produced (trace, lowering, executable);
each sub-audit degrades independently — analysis must never break a
compile.
"""

from . import islands  # noqa: F401  (registry import declares islands)
from .jaxpr_audit import (  # noqa: F401
    Violation, audit_jaxpr, iter_eqns,
)
from . import ast_rules, collectives, donation, hlo_audit  # noqa: F401


def audit_program(program, traced=None, lowered=None, compiled=None, *,
                  const_bytes_limit=None, include_hlo=True, hlo_text=None):
    """Audit one compiled program; returns the ledger ``audit`` dict:
    ``{violations, violation_count, stats, collectives, donation,
    const_bytes}``. Every sub-audit is best-effort — a failure is
    recorded under ``errors`` instead of raised. ``hlo_text`` is the
    executable's text where the caller already took it."""
    from .jaxpr_audit import DEFAULT_CONST_BYTES_LIMIT

    if const_bytes_limit is None:
        const_bytes_limit = DEFAULT_CONST_BYTES_LIMIT
    violations = []
    audit = {"errors": {}}
    closed_jaxpr = getattr(traced, "jaxpr", None) if traced is not None \
        else None

    stats = {}
    if closed_jaxpr is not None:
        try:
            found, stats = audit_jaxpr(
                program, closed_jaxpr,
                const_bytes_limit=const_bytes_limit)
            violations.extend(found)
        except Exception as e:  # noqa: BLE001
            audit["errors"]["jaxpr"] = f"{type(e).__name__}: {e}"
    audit["stats"] = stats
    audit["const_bytes"] = stats.get("const_bytes", 0)

    if not include_hlo:
        hlo_text = None
    elif hlo_text is None and compiled is not None:
        try:
            hlo_text = compiled.as_text()
        except Exception as e:  # noqa: BLE001
            audit["errors"]["hlo_text"] = f"{type(e).__name__}: {e}"
    if hlo_text is not None:
        try:
            found, hlo_stats = hlo_audit.audit_hlo(program, hlo_text)
            violations.extend(found)
            audit["hlo"] = {k: hlo_stats[k]
                            for k in ("f64_ops", "aliased_params")}
        except Exception as e:  # noqa: BLE001
            audit["errors"]["hlo"] = f"{type(e).__name__}: {e}"

    try:
        audit["collectives"] = collectives.collective_summary(
            closed_jaxpr, hlo_text)
    except Exception as e:  # noqa: BLE001
        audit["errors"]["collectives"] = f"{type(e).__name__}: {e}"
        audit["collectives"] = {"op_count": 0, "bytes": 0}

    if compiled is not None:
        try:
            found, summary = donation.audit_donation(
                program, compiled, closed_jaxpr, lowered,
                hlo_text=hlo_text)
            violations.extend(found)
            audit["donation"] = summary
        except Exception as e:  # noqa: BLE001
            audit["errors"]["donation"] = f"{type(e).__name__}: {e}"
            audit["donation"] = {"declared": 0, "aliased": 0,
                                 "dead_count": 0, "dead": []}
    else:
        audit["donation"] = {"declared": 0, "aliased": 0,
                             "dead_count": 0, "dead": []}

    audit["violations"] = [v.as_dict() for v in violations]
    audit["violation_count"] = len(violations)
    if not audit["errors"]:
        del audit["errors"]
    return audit
