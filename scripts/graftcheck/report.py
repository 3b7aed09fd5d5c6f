"""The kernel_audit report: the audit's machine-readable artifact.

`python -m scripts.graftcheck` writes this JSON; surrealdb_tpu/bundle.py
embeds it as the `kernel_audit` debug-bundle section (path via
cnf.KERNEL_AUDIT_REPORT), so `GET /debug/bundle` carries the HLO digest,
declared collectives and rule verdicts of every audited kernel shape
(tests/test_graftcheck.py::test_report_roundtrips_and_validates_in_bundle).
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Tuple

REPORT_SCHEMA = "surrealdb-tpu-kernel-audit/1"


def build_report(results: List[Tuple[dict, dict, object, list]]) -> dict:
    """`results` is [(contract, shape, Lowered, [Finding]), ...] for every
    lowered pair, in audit order."""
    import jax

    kernels: Dict[str, dict] = {}
    total_findings = 0
    for contract, shape, low, findings in results:
        k = kernels.setdefault(
            contract["subsystem"],
            {
                "module": contract["module"],
                "kind": contract["kind"],
                "declared_collectives": sorted(
                    contract.get("allowed_collectives") or ()
                ),
                "declared_out_dtypes": sorted(contract["out_dtypes"]),
                "shapes": {},
                "findings": 0,
            },
        )
        rules = {}
        for rule_id in ("GC001", "GC002", "GC003", "GC004"):
            hits = [f for f in findings if f.rule == rule_id]
            rules[rule_id] = (
                "pass" if not hits else f"fail({len(hits)})"
            )
        k["shapes"][shape["label"]] = {
            "hlo_sha256": low.hlo_sha256,
            "collectives": dict(sorted(low.collectives.items())),
            "out_dtypes": list(low.out_dtypes),
            "rules": rules,
        }
        k["findings"] += len(findings)
        total_findings += len(findings)
    return {
        "schema": REPORT_SCHEMA,
        "generated_ts": time.time(),
        "jax_version": jax.__version__,
        "devices": len(jax.devices()),
        "kernels": kernels,
        "summary": {
            "sites": len(kernels),
            "shapes": sum(len(k["shapes"]) for k in kernels.values()),
            "findings": total_findings,
        },
    }


def write_report(report: dict, path: str) -> str:
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
