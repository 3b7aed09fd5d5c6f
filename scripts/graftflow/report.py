"""The flow_audit report: graftflow's machine-readable artifact.

`python -m scripts.graftflow` writes this JSON (cnf.FLOW_AUDIT_REPORT);
surrealdb_tpu/bundle.py embeds it as the `flow_audit` debug-bundle
section (bundle schema surrealdb-tpu-bundle/5):
tests/test_graftflow.py::test_bundle_embeds_flow_audit_section holds its
call-graph stats above 0 (a silently-degraded analyzer must fail, not
pass vacuously).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Sequence

REPORT_SCHEMA = "surrealdb-tpu-flow-audit/1"


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "baseline.json")


def generate(paths: Optional[Sequence[str]] = None) -> dict:
    """Build the full flow_audit report in-process (the bundle fallback
    for hosts where no `python -m scripts.graftflow` run wrote the report
    file — analysis is pure AST, a few seconds, no jax)."""
    from scripts.baselines import apply_baseline, load_baseline
    from scripts.graftlint.engine import repo_root

    from . import callgraph, rules

    g = callgraph.build(
        list(paths) if paths else [os.path.join(repo_root(), "surrealdb_tpu")]
    )
    findings = rules.run_rules(g)
    new, _stale = apply_baseline(findings, load_baseline(default_baseline_path()))
    return build_report(g, findings, len(findings) - len(new))


def build_report(graph, findings, baselined: int) -> dict:
    """`findings` is the FULL finding list (baselined included) — a rule
    with grandfathered findings reports fail(n), never a vacuous pass."""
    from . import rules as rules_mod

    edges = rules_mod.lock_edges(graph)
    per_rule: Dict[str, int] = {}
    for f in findings:
        per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
    rules: Dict[str, str] = {}
    for rid in sorted(rules_mod.RULES):
        n = per_rule.get(rid, 0)
        rules[rid] = "pass" if n == 0 else f"fail({n})"
    acq_sites = sum(len(fi.acquires) for fi in graph.functions.values())
    return {
        "schema": REPORT_SCHEMA,
        "generated_ts": time.time(),
        "callgraph": {
            "modules": len(graph.modules),
            "nodes": len(graph.functions),
            "edges": graph.call_edges,
            "boundary_edges": graph.boundary_edges,
            "unresolved_calls": graph.unresolved_calls,
            "lock_sites": len(graph.lock_sites),
            "lock_names": sorted(graph.lock_names),
            "acquisition_sites": acq_sites,
        },
        "lock_graph": {
            "edges": [
                {
                    "from": a,
                    "to": b,
                    "site": f"{w['rel']}:{w['line']}",
                    "via": w.get("via"),
                }
                for (a, b), w in sorted(edges.items())
            ],
        },
        "rules": rules,
        "summary": {
            "findings": len(findings),
            "baselined": baselined,
            "new": len(findings) - baselined,
        },
    }


def write_report(report: dict, path: str) -> str:
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
