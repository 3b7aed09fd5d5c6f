"""BENCHMARK.json and the files its names resolve to.

Everything that belongs to one configuration, one traffic mix, one kind of
deployment, one per-layer metric or one kernel is a file of its own under
`benchmarks/`, found by name. `problems()` lists what does not resolve or
does not agree; `run.py` refuses to start on a non-empty list, and
`benchmarks/tests` holds the committed manifest to an empty one.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import re
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LOOPS, TRANSPORTS = ("closed",), ("ws_rpc",)  # what harness/loadgen.py implements
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(bench_dir: str, kind: str, name: str) -> dict:
    with open(os.path.join(bench_dir, kind, name + ".json")) as f:
        return json.load(f)


def load_modules(bench_dir: str, kind: str, key: str) -> Dict[str, object]:
    """Every `<bench_dir>/<kind>/*.py`, imported by path and keyed by its
    own `key` attribute (or by file name where `key` is None)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(bench_dir, kind, "*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[stem if key is None else getattr(mod, key)] = mod
    return out


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, group: str, workload: str) -> List[dict]:
    """The metrics of `group` that the cell reports."""
    return [m for m in manifest[group] if "workloads" not in m or workload in m["workloads"]]


def mix_problems(bench_dir: str, w: dict, configs: dict) -> List[str]:
    """What the one load generator cannot run of the cell's mix as written."""
    mix, bad = load_json(bench_dir, "traffic", w["traffic"]), []
    if mix.get("loop") not in LOOPS:
        bad.append(f"mix {w['traffic']} has loop {mix.get('loop')!r}; the generator runs {LOOPS}")
    if mix.get("transport") not in TRANSPORTS:
        bad.append(f"mix {w['traffic']} has transport {mix.get('transport')!r}; the generator speaks {TRANSPORTS}")
    path = os.path.join(os.path.dirname(bench_dir), configs.get(w["config"], {}).get("file", ""))
    if os.path.isfile(path):
        with open(path) as f:
            known = json.load(f).get("statements", {})
        for st in mix.get("statements", []):
            if "dispatches" not in known.get(st["name"], {}):
                bad.append(f"mix {w['traffic']} sends statement {st['name']!r}, of which the "
                           f"configuration states no `dispatches`")
    return bad


def problems(manifest: dict, bench_dir: str = BENCH_DIR) -> List[str]:
    bad: List[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys are {sorted(manifest)}, not {sorted(TOP_KEYS)}")
        return bad
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]]
    names += [w["name"] for w in manifest["workloads"]] + [c["name"] for c in manifest["configs"]]
    for n in names + [w["traffic"] for w in manifest["workloads"]]:
        if not NAME_RE.match(n):
            bad.append(f"name {n!r} is outside the allowed characters")
    for group in ("end_to_end", "per_layer", "workloads", "configs"):
        seen = [m["name"] for m in manifest[group]]
        if len(seen) != len(set(seen)):
            bad.append(f"{group} repeats a name")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']} is outside the allowed characters")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better is {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source is {m['source']!r}")
    configs = {c["name"]: c for c in manifest["configs"]}
    kinds = load_modules(bench_dir, "deployments", "KIND")
    kernels = load_modules(bench_dir, "kernels", None)
    for c in manifest["configs"]:
        path = os.path.join(os.path.dirname(bench_dir), c["file"])
        if not os.path.isfile(path):
            bad.append(f"config {c['name']}: no file {c['file']}")
            continue
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("kind") not in kinds:
            bad.append(f"config {c['name']}: no deployments/*.py of kind {cfg.get('kind')!r}")
        if cfg.get("kernel") not in kernels:
            bad.append(f"config {c['name']}: no kernels/{cfg.get('kernel')}.py")
        if sorted(cfg.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: `reduced` differs between manifest and file")
    cells = [w["name"] for w in manifest["workloads"]]
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: no config {w['config']!r}")
        if not os.path.isfile(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")):
            bad.append(f"workload {w['name']}: no traffic/{w['traffic']}.json")
        else:
            bad += [f"workload {w['name']}: {p}" for p in mix_problems(bench_dir, w, configs)]
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips is {w['chips']}")
        if not [m for m in metrics_of(manifest, "end_to_end", w["name"]) if m["name"] != "setup_s"]:
            bad.append(f"workload {w['name']} reports no end-to-end metric besides setup_s")
        if not metrics_of(manifest, "per_layer", w["name"]):
            bad.append(f"workload {w['name']} reports no per-layer metric")
    if "setup_s" not in [m["name"] for m in manifest["end_to_end"]]:
        bad.append("no end-to-end metric named setup_s")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    readers = load_modules(bench_dir, "layer_metrics", "NAME")
    for m in manifest["per_layer"]:
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: lists no such workload {w!r}")
        r = readers.get(m["name"])
        if r is None:
            bad.append(f"per-layer metric {m['name']}: no layer_metrics/*.py with that NAME")
        else:
            for key, attr in (("unit", "UNIT"), ("layer", "LAYER"), ("moves", "MOVES"), ("source", "SOURCE")):
                if getattr(r, attr) != m[key]:
                    bad.append(f"{m['name']}: {key} {m[key]!r} differs from its reader's {getattr(r, attr)!r}")
        target = e2e.get(m["moves"])
        if target is None:
            bad.append(f"{m['name']}: moves {m['moves']!r}, which is no end-to-end metric")
            continue
        for w in m.get("workloads", cells):
            if "workloads" in target and w not in target["workloads"]:
                bad.append(f"{m['name']}: moves {m['moves']}, which cell {w} does not report")
    return bad
