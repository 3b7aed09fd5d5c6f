"""End-to-end benchmark: all 5 BASELINE.md north-star configs through ds.execute().

Every timed query runs the full engine path — parse, plan, index/mirror,
kernel dispatch, result materialisation — via `Datastore.execute()`. Nothing
is kernel-only. The CPU baseline for each config re-runs the SAME SurrealQL
with the device gate off (cnf.TPU_DISABLE, the in-process equivalent of
SURREAL_TPU_DISABLE=1), which forces every kernel gate onto the host/numpy
twin paths.

Configs (BASELINE.md "North-star configs"):
  1. graph_3hop   — SELECT count(->knows->person->...) 3-hop chains over a
                    10k-node / 1M-edge social graph; value = edges/sec
                    traversed (hop1+hop2+hop3 path counts per seed).
  2. knn_ivf      — SELECT id FROM item WHERE emb <|10,64|> $q through the
                    DEFINEd HNSW index (IVF ANN path) at 1M x 768; recall@10
                    measured against exact float32 ground truth; the exact
                    device path is reported side by side.
  3. bm25_topk    — SELECT ... WHERE body @1@ 'w1 w2' ORDER BY score DESC
                    LIMIT 10 over 1M FT-indexed docs.
  4. hybrid       — kNN prefilter + WHERE flag + 2-hop graph expand per hit,
                    over the same 1M-node corpus.
  5. ml_scan      — SELECT ml::scorer<1>(emb) over a full 1M-row table scan
                    (one batched forward dispatch per scan).

Output: one JSON line per config {"metric", "value", "unit", "vs_baseline",
...extras}, then a final headline line (north-star kNN QPS, vs_baseline =
geometric mean of all configs' ratios).

Driver-proof evidence: every emit line is buffered,
the full block is re-printed at the end (so a truncated stdout tail still
carries every config), and the whole run is written to
`bench_results_<round>.json` next to this file. Each per-config line
carries `config`, `errors`, `retries`, `strategy` and `batch` accounting
pulled from the engine's telemetry counters, PLUS (r6, the instrument for
the r5 scale-1.0 kNN collapse) `error_breakdown` — per-class deltas across
statement/dispatch/rpc error counters — and `slowest_trace`, the full
request-scoped span tree (tracing.py) of the config's slowest query, so
"where did the time go / what failed" is answerable from the artifact
alone. The artifact is schema-checked by scripts/check_bench_artifact.py,
invoked automatically after the write.

Env knobs: SURREAL_BENCH_SCALE (default 1.0 — scales the 1M corpora),
SURREAL_BENCH_CONFIGS (default "1,2,3,4,5"), SURREAL_BENCH_OUT (artifact
path; default bench_results_r06.json), SURREAL_PROFILE=1 or --profile
(enable span recording AND capture a jax.profiler device trace into
`bench_trace_<round>/` next to the artifact; a trace that cannot start
fails the run).

Note on timing: one bare jitted dispatch+fetch round trip is measured at
start-up and reported as rtt_ms; every engine-path latency includes at
least one. The process exits non-zero when a config errored, a concurrent
query failed, or the artifact validator rejected the artifact.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

SCALE = float(os.environ.get("SURREAL_BENCH_SCALE", "1.0"))
CONFIGS = set(os.environ.get("SURREAL_BENCH_CONFIGS", "1,2,3,4,5,6,7,8,9,10,11,12,13").split(","))
ROUND = os.environ.get("SURREAL_BENCH_ROUND", "r10")
OUT_PATH = os.environ.get(
    "SURREAL_BENCH_OUT",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), f"bench_results_{ROUND}.json"),
)
PROFILE = "--profile" in sys.argv[1:] or os.environ.get("SURREAL_PROFILE") == "1"
# schema/7 (r11, ingest pipeline v2): every config line carries
# `ingest_rate_rows_s` — the CUMULATIVE bulk-load rows/sec through
# ds.execute() across every ingest the run performed up to that config
# (rows pre-built; the engine path is what is measured; one shared corpus
# feeds several configs, so the rate is run-cumulative by construction) —
# so ingest regressions can't hide in setup time. Config 6
# additionally carries an `ingest` object: the SUSTAINED mirrored-table
# phase (bulk op + immediately-serving columnar query per round) measured
# with the delta feed off (the r10 re-scan semantics) and on, with the
# ratio and a zero-staleness parity flag. Config 7's cluster object gains
# ingest fields (rate + routed-bulk-path proof). Everything schema/6
# carried stays.
# schema/8 (r12, fault tolerance): new config 8 — a CHAOS window over a
# 3-node replicated (SURREAL_CLUSTER_RF) cluster that kills one node
# mid-window and keeps reading: its line carries a `chaos` object
# (nodes/rf/killed_node, failover_reads, degraded_responses, errors,
# wrong_answers — MUST be 0 — and recovery_s, the time from the kill to
# the next successful read). Config 7's cluster object gains `rf` and its
# row-spread accounting is replication-aware. The embedded debug bundle
# grew its eighth section (`faults`: failpoint trip counters).
# schema/9 (r13, cluster observability): the embedded bundle grew its
# NINTH section (`events`: the structured trace-linked timeline), and the
# cluster configs (7, 8) each carry a `cluster_obs` object — the FEDERATED
# cluster bundle scraped from the coordinator (per-node sections; a killed
# node shows up `unreachable`) plus the slowest scattered statement's
# per-shard profile (per-node RPC ms, rows, retries, failovers, merge ms)
# and the live-node list its shard timings must cover. Config 8's chaos
# line adds an `events` accounting (breaker events, degraded reads and
# how many of those carry no trace_id — bench_gate floors them).
# schema/10 (r14, vectorized SELECT pipeline): new config 9 `ordered_agg` —
# ORDER BY+LIMIT and GROUP BY aggregate statements measured columnar vs
# row path on IDENTICAL data, each with `same_results` asserted, plus the
# window's `column_pipeline{outcome}` counter snapshot (every
# decline-to-row-path is counted — zero silent wrong answers is a
# validator rule, not a hope). Config 7's cluster object gains
# `agg_pushdown`: the coordinator merged per-shard PARTIAL aggregates
# (two-phase, like BM25 global stats) instead of shipping rows, proven by
# the cluster_agg{outcome=pushed} counter and per-shard partial counts.
# schema/11 (r15, elastic cluster): new config 10 `elastic_chaos` — a
# 3-node RF=2 cluster serving reads while one node is KILLED mid-window
# and a REPLACEMENT node joins (membership epoch bump + background shard
# migration streamed as LWW bulk ingest), then anti-entropy sweeps run to
# convergence. Its line carries an `elastic` object (killed/joined node,
# epoch, wrong_answers — MUST be 0 — lost_acked_writes — MUST be 0 —
# migration_rows, repaired counts and repair_s, the kill->converged repair
# time bench_gate ceilings). The bundle engine.cluster section gains
# epoch/membership/migration/repair and bench_diff --bundles flags a
# member stuck on an old epoch as peer drift.
# schema/12 (r16, workload statistics plane): every config line carries a
# `statements` object — the window's top statement FINGERPRINTS (stats.py:
# calls, latency quantiles, rows, plan-mix vector, plan-flip log; the
# store is reset per accounting window so the embed is per-config) and the
# sampling profiler's window summary (samples per `bg:`-named thread kind
# and per fingerprint). The config-2 line adds `profiler_overhead`: the
# paired sampler-on/off A/B whose <=3% ceiling bench_gate enforces. The
# embedded bundle is surrealdb-tpu-bundle/6 (sections 12 `statements` +
# 13 `profiler`), and `bench_diff --statements` names per-fingerprint
# qps/p99 regressions and plan-mix flips between two artifacts.
# schema/13 (r17, tenant cost-attribution plane): every config line
# carries a `tenants` object — the window's per-(ns, db) resource meters
# (accounting.py: cpu/exec/dispatch seconds, rows, bytes, bg + scatter
# cost, reset per accounting window like the stats store). New config 11
# `multi_tenant`: a 2-node cluster serving THREE namespaces (one abusive,
# two well-behaved) whose line proves CONSERVATION (per-tenant sums vs
# the global counters, <=1% — validator-enforced), attributes >=90% of
# the excess to the abusive tenant, carries the budget-breach event
# (trace-linked to the offending statement) and the federated node-tagged
# `GET /tenants?cluster=1` view. The config-2 line adds
# `accounting_overhead`: the paired accounting-on/off A/B whose <=3%
# ceiling bench_gate enforces. The embedded bundle is
# surrealdb-tpu-bundle/7 (section 14 `tenants`), and `bench_diff
# --tenants` names per-tenant share shifts between two artifacts.
# schema/14 (r18, advisor plane): new config 12 `advisor_shift` — a
# SHIFTING workload (scan-heavy -> point-lookup -> vector-heavy phases
# over dedicated tables, stats/accounting reset at each transition so a
# phase is one observation window) whose line carries an `advisor`
# object: per-phase proposal snapshots with the statements/tenants
# embeds their evidence chains resolve against. The validator asserts
# the phase-appropriate proposals (`index.create` in the scan phase;
# its expiry plus `ivf.retrain` — a deliberately outgrown quantizer —
# by the vector phase) and that every evidence pointer resolves
# in-artifact. The config-2 line adds `advisor_overhead`: the paired
# sweeps-live/parked A/B (at a deliberately hostile 0.25s interval)
# whose <=3% ceiling bench_gate enforces, same contract as the profiler
# and accounting planes. The embedded bundle is surrealdb-tpu-bundle/8
# (section 15 `advisor`), and `bench_diff --advisor` names proposals
# that appeared/resolved/flapped between two artifacts.
# schema/15 (r19, plan cache): every config line carries a `plan_cache`
# object — the fingerprint-keyed plan-cache window stats (hit/miss/route
# counters, invalidation causes, verify outcomes, per-fingerprint
# pre-kernel parse+plan averages warm vs cold) — because _acct_begin now
# resets the cache's measurement window alongside the other planes. The
# config-2/6/9 lines add `plan_cache_parity`: the SAME query battery run
# cold (cache cleared) then warm (every shape installed), transcripts
# byte-compared (`parity` must be true — 0 stale serves, measured not
# assumed) with the warm hit rate and the cold-vs-warm pre-kernel split
# whose >=2x floor scripts/bench_gate.py enforces on config 2. The
# embedded bundle is surrealdb-tpu-bundle/9 (section 16 `plan_cache`).
# schema/16 (r20, C1M network plane): new config 13 `c1m_net` — the
# event-loop ingress at connection scale: >=20k idle in-process
# connections attached (per-connection memory measured under
# tracemalloc), then >=2k active connections each completing an HTTP
# request with ZERO errors (accept-to-first-byte p50/p99 from the
# loop's own TTFB ring), then the per-tenant weighted-fair QoS proof —
# a victim tenant's fixed battery timed solo and again under an
# abusive tenant's sustained flood (quota-capped, bounded admission
# queue): the victim's contended p99 must stay within bench_gate's 3x
# ceiling while the abuser's overflow is SHED (counted 503s, never
# unbounded buffering). The embedded bundle is surrealdb-tpu-bundle/10
# (section 17 `net`: live servers + admission/QoS state).
SCHEMA = "surrealdb-tpu-bench/16"

D = 768
NI = max(int(1_000_000 * SCALE), 1024)  # item corpus (configs 2/4/5)
ND = max(int(1_000_000 * SCALE), 1024)  # FT docs (config 3)
NP_NODES = max(int(10_000 * min(SCALE * 10, 1.0)), 100)  # person nodes
NE = max(int(1_000_000 * SCALE), 1000)  # person->knows edges
EH_REGION = min(NI, 262_144)  # hybrid edges live among the first items
EH_DEG = 4  # out-degree inside that region

_T0 = time.time()


def log(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


RESULTS: list = []  # every emitted line, in order (the driver-proof buffer)
_DEFER = False  # inside a config: buffer only; run_cfg prints enriched lines
FAILURES: list = []  # what makes main() exit non-zero (logged as it happens)


def fail(msg: str) -> None:
    FAILURES.append(msg)
    log(f"FAILED: {msg}")


def emit(obj: dict) -> None:
    RESULTS.append(obj)
    if not _DEFER:
        print(json.dumps(obj), flush=True)


def _strategy_counts() -> dict:
    """Current {strategy: count} across the planner + kNN strategy counters."""
    from surrealdb_tpu import telemetry

    out: dict = {}
    for family in ("plan_strategy", "knn_strategy"):
        for labels, v in telemetry.counters_matching(family).items():
            out[dict(labels).get("strategy", "?")] = out.get(
                dict(labels).get("strategy", "?"), 0
            ) + int(v)
    return out


def _error_counts() -> dict:
    """Current error totals: failed statements, permanently-failed dispatch
    batches, RPC-level errors."""
    from surrealdb_tpu import telemetry

    return {
        "statements": int(sum(telemetry.counters_matching("statement_errors").values())),
        "dispatch": int(sum(telemetry.counters_matching("dispatch_failures").values())),
        "rpc": int(sum(telemetry.counters_matching("rpc_errors").values())),
    }


def _scan_counts() -> dict:
    """Columnar-scan path accounting: strategy counts + predicate
    compile outcomes (idx/column_mirror.py, ops/predicates.py)."""
    from surrealdb_tpu import telemetry

    out: dict = {}
    for labels, v in telemetry.counters_matching("scan_strategy").items():
        out[f"strategy:{dict(labels).get('strategy', '?')}"] = int(v)
    for labels, v in telemetry.counters_matching("predicate_compile_outcome").items():
        out[f"predicate:{dict(labels).get('outcome', '?')}"] = int(v)
    for labels, v in telemetry.counters_matching("knn_prefilter").items():
        out[f"knn_prefilter:{dict(labels).get('outcome', '?')}"] = int(v)
    return out


def _error_classes() -> dict:
    """Per-class error/retry totals across every error-counter family —
    `{family:class: count}` (the r5 action item: an anomalous config must
    say WHICH errors it took, not just how many)."""
    from surrealdb_tpu import telemetry

    out: dict = {}
    for fam, label in (
        ("statement_errors", "kind"),
        ("dispatch_failures", "error"),
        ("dispatch_retries", "cause"),
        ("rpc_errors", "error"),
    ):
        for labels, v in telemetry.counters_matching(fam).items():
            key = f"{fam}:{dict(labels).get(label, '?')}"
            out[key] = out.get(key, 0) + int(v)
    return out


def _pcts(times) -> dict:
    """p50/p95/p99 (ms) of a per-query latency sample."""
    if not times:
        return {"p50": None, "p95": None, "p99": None}
    ts = sorted(times)

    def at(p):
        return round(ts[min(int(len(ts) * p), len(ts) - 1)] * 1e3, 1)

    return {"p50": at(0.50), "p95": at(0.95), "p99": at(0.99)}


def _acct_begin(ds) -> dict:
    from surrealdb_tpu import profiler, stats, tracing

    # fresh store per accounting window: slowest_trace selection and the
    # truncation flag are then per-window facts, and the store can never
    # fill mid-window from prior configs' traces (bench owns the process)
    tracing.store_reset()
    # same per-window reset for the workload statistics plane: the
    # config line's top-fingerprint embed and profiler summary are then
    # per-config facts (bench owns the process)
    stats.reset()
    profiler.reset()
    # and for the tenant cost-attribution plane: per-window meters mean
    # the conservation check compares like with like
    from surrealdb_tpu import accounting

    accounting.reset()
    # and for the advisor plane: proposals derived from a prior config's
    # evidence must not leak into this window's line
    from surrealdb_tpu import advisor

    advisor.reset()
    # and for the plan cache: zero the window counters/timing but KEEP
    # the installed entries — a config window measures its own hit rate
    # and pre-kernel split without forgetting shapes earlier configs warmed
    ds.plan_cache.reset_window()
    return {
        "t0": time.time(),
        "stats": ds.dispatch.stats(),
        "widths": ds.dispatch.width_distribution(),
        "errors": _error_counts(),
        "strategy": _strategy_counts(),
        "classes": _error_classes(),
        "scan": _scan_counts(),
        "trace_ids": set(tracing.trace_ids()),
    }


def _slow_in_window(t0: float):
    """(records, truncated): slow-statement records from the telemetry ring
    since t0 — logged per config (every config window runs AFTER its
    ingest) and counted in the artifact, so 'no slow statement over 5s
    after ingest' is checkable from either the log or the JSON. The ring
    is a bounded FIFO: when it is full AND its oldest survivor is already
    inside the window, earlier window records may have been evicted —
    `truncated` flags that instead of letting eviction fabricate a zero."""
    from surrealdb_tpu import telemetry

    entries = telemetry.slow_queries()
    inwin = [e for e in entries if e.get("ts", 0) >= t0]
    cap = getattr(telemetry, "_SLOW_LOG_SIZE", 128)
    truncated = len(entries) >= cap and bool(entries) and entries[0].get("ts", 0) >= t0
    return inwin, truncated


def _acct_delta(ds, before: dict) -> dict:
    """Per-config accounting delta pulled from the telemetry counters — the
    fields that make a bench line attributable after the fact."""
    from surrealdb_tpu import tracing

    st0, st1 = before["stats"], ds.dispatch.stats()
    e0, e1 = before["errors"], _error_counts()
    s0, s1 = before["strategy"], _strategy_counts()
    c0, c1 = before["classes"], _error_classes()
    dd = {k: st1[k] - st0[k] for k in st1}
    # the full span tree of this config's slowest request (TRACE_SAMPLE is
    # forced to 1.0 for the bench process, so every query's trace is
    # available at window close)
    new_traces = [
        t
        for tid in tracing.trace_ids()
        if tid not in before["trace_ids"]
        for t in (tracing.get_trace(tid),)
        if t is not None
    ]
    slowest = max(new_traces, key=lambda t: t["duration_ms"], default=None)
    from surrealdb_tpu import cnf as _cnf

    # a full store at window close means FIFO eviction may have dropped
    # the true slowest — flag it instead of attributing to a survivor
    truncated = len(tracing.trace_ids()) >= _cnf.TRACE_STORE_SIZE
    w0, w1 = before["widths"], ds.dispatch.width_distribution()
    width_dist = {
        str(w): n - w0.get(w, 0) for w, n in sorted(w1.items()) if n - w0.get(w, 0)
    }
    sc0, sc1 = before["scan"], _scan_counts()
    slow_entries, slow_truncated = _slow_in_window(before["t0"])
    # flight-recorder overlap accounting (structural, replaces the r6
    # ann_training_overlap flag): which background tasks ran inside this
    # window, per kind with overlap durations; plus every XLA compile in
    # the window with its prewarm/on-demand attribution
    from surrealdb_tpu import bg, compile_log

    t1 = time.time()
    win_tasks = bg.window(before["t0"], t1)
    kinds: dict = {}
    for t in win_tasks:
        k = kinds.setdefault(
            t["kind"], {"count": 0, "overlap_s": 0.0, "stalled": 0}
        )
        k["count"] += 1
        k["overlap_s"] = round(k["overlap_s"] + t.get("overlap_s", 0.0), 4)
        k["stalled"] += 1 if t["stalled"] else 0
    win_compiles = [e for e in compile_log.events(since=before["t0"]) if e["ts"] <= t1]
    from surrealdb_tpu import profiler, stats

    return {
        # workload statistics plane (schema/12): this window's top
        # statement shapes + the sampler's window summary — per-config
        # because _acct_begin reset both stores
        "statements": {
            "top": stats.statements(limit=8),
            "profiler": profiler.summary(),
        },
        # tenant cost-attribution plane (schema/13): this window's
        # per-(ns, db) meters + the conservation totals they must sum to
        "tenants": _tenants_embed(),
        # plan-cache plane (schema/15): this window's hit/miss/verify
        # counters + per-fingerprint pre-kernel averages (warm vs cold)
        "plan_cache": ds.plan_cache.window_stats(),
        "bg_tasks": {
            "kinds": kinds,
            "tasks": [
                {
                    "kind": t["kind"], "target": t["target"], "state": t["state"],
                    "overlap_s": t.get("overlap_s"), "stalled": t["stalled"],
                    "trace_id": t["trace_id"],
                }
                for t in win_tasks[:20]
            ],
        },
        "compiles": {
            "on_demand": sum(1 for e in win_compiles if e["mode"] == "on_demand"),
            "prewarm": sum(1 for e in win_compiles if e["mode"] == "prewarm"),
            "startup": sum(1 for e in win_compiles if e["mode"] == "startup"),
            "events": win_compiles[:20],
        },
        "errors": {k: e1[k] - e0[k] for k in e1},
        "scan": {k: v - sc0.get(k, 0) for k, v in sc1.items() if v - sc0.get(k, 0)},
        "error_breakdown": {
            k: v - c0.get(k, 0) for k, v in c1.items() if v - c0.get(k, 0)
        },
        "retries": int(dd["retries"]),
        "splits": int(dd["splits"]),
        "strategy": {k: v - s0.get(k, 0) for k, v in s1.items() if v - s0.get(k, 0)},
        "batch": {
            "submitted": int(dd["submitted"]),
            "dispatches": int(dd["dispatches"]),
            "batched": int(dd["batched"]),
            "mean_width": round(dd["submitted"] / dd["dispatches"], 3)
            if dd["dispatches"]
            else None,
            "width_dist": width_dist,
            "launch_s": round(dd["launch_s"], 4),
            "collect_s": round(dd["collect_s"], 4),
            "pipeline_wait_s": round(dd["pipeline_wait_s"], 4),
        },
        "slowest_trace": slowest,
        "trace_window_truncated": truncated,
        "slow_over_5s": sum(
            1 for e in slow_entries if e.get("duration_s", 0) > 5.0
        ),
        "slow_window_truncated": slow_truncated,
        # private: run_cfg pops this for log replay (never serialized)
        "_slow_entries": slow_entries,
    }


# ------------------------------------------------------------------ helpers
def run(ds, s, sql, vars=None):
    out = ds.execute(sql, s, vars=vars)
    for r in out:
        if r["status"] != "OK":
            raise RuntimeError(f"query failed: {r.get('result')!r} for {sql[:120]}")
    return out


def timed_queries(ds, s, queries, warmup=1):
    """Run [(sql, vars)] sequentially through ds.execute; returns
    (qps, p50_ms, results). Warmup runs the first query (compile/mirror)."""
    for sql, v in queries[:warmup]:
        run(ds, s, sql, v)
    times, results = [], []
    for sql, v in queries:
        t0 = time.perf_counter()
        out = run(ds, s, sql, v)
        times.append(time.perf_counter() - t0)
        results.append(out[-1]["result"])
    total = sum(times)
    return len(queries) / total, sorted(times)[len(times) // 2] * 1e3, results


def cpu_mode(on: bool) -> None:
    from surrealdb_tpu import cnf

    cnf.TPU_DISABLE = on


def measure_rtt() -> float:
    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.ones((8, 8)))
    f = jax.jit(lambda a: (a @ a).sum())
    _ = float(f(x))
    t0 = time.perf_counter()
    for _ in range(5):
        _ = float(f(x))
    return (time.perf_counter() - t0) / 5


def vec_rows(vecs, ids, flag_every=0):
    # embeddings stay numpy end-to-end (packed-vector values, ser.py EXT_VEC):
    # no tolist()/asarray round trip per row
    rows = []
    for j, i in enumerate(ids):
        # `val` feeds config 6's selective filtered-SELECT predicate
        r = {"id": int(i), "emb": vecs[j], "val": int(i) % 1000}
        if flag_every:
            r["flag"] = bool(i % flag_every == 0)
        rows.append(r)
    return rows


N_CLUSTERS = 4000
CLUSTER_SIGMA = 0.35


def gen_corpus(n, d, seed=42):
    """Deterministic clustered corpus (mixture of gaussians: 4000 centers,
    sigma 0.35). Real embedding spaces are clustered — isotropic gaussian
    noise has NO neighborhood structure (every point's true top-k is spread
    uniformly over the corpus), which makes any sublinear ANN meaningless
    rather than hard. Standard ANN benchmark sets (SIFT/GloVe/DEEP) are all
    clustered; this mirrors them while staying generatable on the fly."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((N_CLUSTERS, d)).astype(np.float32)
    out = np.empty((n, d), dtype=np.float32)
    step = 65_536
    for i in range(0, n, step):
        m = min(step, n - i)
        cid = rng.integers(0, N_CLUSTERS, size=m)
        out[i : i + m] = centers[cid] + CLUSTER_SIGMA * rng.standard_normal(
            (m, d), dtype=np.float32
        )
    return out


# ------------------------------------------------------------------ ingest
# process-wide bulk-load accounting behind every config's
# `ingest_rate_rows_s` line: rows/sec THROUGH ds.execute() — row payloads
# are pre-built outside the timed window so the engine path is what is
# measured, and regressions can't hide in setup time
_INGEST = {"rows": 0, "secs": 0.0}


def ingest_run(ds, s, sql, batches):
    """Run one bulk statement per batch. Each batch's rows materialize
    BEFORE its timed window (payload building is client work), and only
    the execute() is accounted — memory stays bounded at one batch."""
    n = 0
    for rows in batches:
        rows = list(rows)
        t0 = time.perf_counter()
        run(ds, s, sql, {"rows": rows})
        _INGEST["secs"] += time.perf_counter() - t0
        n += len(rows)
    _INGEST["rows"] += n
    return n


def ingest_rate():
    return round(_INGEST["rows"] / _INGEST["secs"], 1) if _INGEST["secs"] else None


def ingest_person_graph(ds, s, rng):
    log(f"ingest person graph: {NP_NODES} nodes, {NE} edges")
    run(ds, s, "DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS")
    B = 25000
    ingest_run(
        ds, s, "INSERT INTO person $rows RETURN NONE",
        ([{"id": j} for j in range(i, min(i + B, NP_NODES))]
         for i in range(0, NP_NODES, B)),
    )
    from surrealdb_tpu.sql.value import Thing

    pairs = rng.integers(0, NP_NODES, size=(NE, 2))
    ingest_run(
        ds, s, "INSERT RELATION INTO knows $rows RETURN NONE",
        ([{"in": Thing("person", int(a)), "out": Thing("person", int(b))}
          for a, b in pairs[i : i + B]]
         for i in range(0, NE, B)),
    )
    log(f"person graph done ({ingest_rate()} rows/s cumulative)")


def ingest_items(ds, s, corpus):
    log(f"ingest items: {NI} x {D} with HNSW index")
    run(
        ds,
        s,
        "DEFINE TABLE item SCHEMALESS; "
        f"DEFINE INDEX iemb ON item FIELDS emb HNSW DIMENSION {D} DIST EUCLIDEAN EFC 64",
    )
    B = 20000
    for i in range(0, NI, B):
        ids = range(i, min(i + B, NI))
        ingest_run(
            ds, s, "INSERT INTO item $rows RETURN NONE",
            [vec_rows(corpus[i : i + B], ids, flag_every=4)],
        )
        if i and i % 200_000 == 0:
            log(f"  items {i}/{NI}")
    log(f"items done ({ingest_rate()} rows/s cumulative)")


def ingest_hybrid_edges(ds, s, rng):
    n_edges = EH_REGION * EH_DEG
    log(f"ingest hybrid edges: {n_edges} rel edges among first {EH_REGION} items")
    run(ds, s, "DEFINE TABLE rel SCHEMALESS")
    from surrealdb_tpu.sql.value import Thing

    B = 25000
    srcs = np.repeat(np.arange(EH_REGION), EH_DEG)
    dsts = rng.integers(0, EH_REGION, size=n_edges)
    ingest_run(
        ds, s, "INSERT RELATION INTO rel $rows RETURN NONE",
        ([{"in": Thing("item", int(a)), "out": Thing("item", int(b))}
          for a, b in zip(srcs[i : i + B], dsts[i : i + B])]
         for i in range(0, n_edges, B)),
    )
    log("hybrid edges done")


VOCAB_N = 2000


def _vocab():
    return [f"w{i:04d}" for i in range(VOCAB_N)]


def ingest_docs(ds, s, rng):
    log(f"ingest docs: {ND} FT-indexed")
    run(
        ds,
        s,
        "DEFINE ANALYZER simple TOKENIZERS blank FILTERS lowercase; "
        "DEFINE TABLE doc SCHEMALESS; "
        "DEFINE INDEX fbody ON doc FIELDS body SEARCH ANALYZER simple BM25",
    )
    vocab = np.asarray(_vocab())
    # zipf-ish: word rank r sampled with p ~ 1/(r+10)
    w = 1.0 / (np.arange(VOCAB_N) + 10.0)
    p = w / w.sum()
    B = 20000
    L = 12
    for i in range(0, ND, B):
        n = min(B, ND - i)
        words = vocab[rng.choice(VOCAB_N, size=(n, L), p=p)]
        ingest_run(
            ds, s, "INSERT INTO doc $rows RETURN NONE",
            [[{"id": int(i + j), "body": " ".join(words[j])} for j in range(n)]],
        )
        if i and i % 200_000 == 0:
            log(f"  docs {i}/{ND}")
    log(f"docs done ({ingest_rate()} rows/s cumulative)")


# ------------------------------------------------------------------ configs
def bench_graph_3hop(ds, s, rng):
    chain = "->knows->person->knows->person->knows->person"
    seeds = rng.integers(0, NP_NODES, size=8).tolist()
    # calibrate edges traversed per seed = hop1 + hop2 + hop3 path counts.
    # Calibration runs in CPU mode: the counts are identical and the device
    # path would compile a distinct fused shape per chain length just to
    # produce constants.
    cpu_mode(True)
    edges_per_seed = {}
    for seed in seeds:
        tot = 0
        for hops in range(1, 4):
            c = "->knows->person" * hops
            out = run(ds, s, f"SELECT count({c}) AS c FROM person:{seed}")
            tot += out[-1]["result"][0]["c"]
        edges_per_seed[seed] = tot
    cpu_mode(False)

    # join the ingest-armed mirror build + count-kernel prewarm
    # (idx/graph_csr.py): the timed pass must start on compiled shapes,
    # not inside an XLA compile (the r5 84.8s/26.4s first-query stalls)
    ds.graph_mirrors.wait_prewarm(timeout=300)

    # sequential pass: per-query latency (at least one dispatch round trip)
    queries = [(f"SELECT count({chain}) AS c FROM person:{seed}", None) for seed in seeds]
    qps, p50, _ = timed_queries(ds, s, queries)
    seq_eps = sum(edges_per_seed.values()) / (len(queries) / qps)

    # concurrent pass: dispatch coalescing batches count chains into one
    # dense-matmul launch (idx/graph_csr.py chain_count_batch_dense)
    import threading

    stats0 = ds.dispatch.stats()
    nthreads, rounds = 32, 2
    conc_seeds = [seeds[i % len(seeds)] for i in range(nthreads * rounds)]
    errors = []
    conc_times = []
    barrier = threading.Barrier(nthreads + 1)

    def client(i):
        barrier.wait()
        for r_ in range(rounds):
            seed = conc_seeds[i * rounds + r_]
            tq = time.perf_counter()
            try:
                run(ds, s, f"SELECT count({chain}) AS c FROM person:{seed}")
                conc_times.append(time.perf_counter() - tq)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(nthreads)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    conc_dt = time.perf_counter() - t0
    mean_edges = sum(edges_per_seed.values()) / len(edges_per_seed)
    edges_done = sum(edges_per_seed[sd] for sd in conc_seeds) - len(errors) * mean_edges
    if errors:
        fail(f"graph: {len(errors)} concurrent queries failed; first: {errors[0]!r:.300}")
    conc_eps = edges_done / conc_dt if conc_dt > 0 else 0.0
    d1 = ds.dispatch.stats()
    dstats = {k: d1[k] - stats0[k] for k in d1}

    # CPU baseline: the host twin sequentially (its best single-process
    # rate — python host walks do not scale with threads)
    cpu_mode(True)
    cq = queries[:2]
    t0 = time.perf_counter()
    for sql, v in cq:
        run(ds, s, sql, v)
    cpu_dt = time.perf_counter() - t0
    cpu_mode(False)
    cpu_eps = sum(edges_per_seed[s_] for s_ in seeds[:2]) / cpu_dt

    emit(
        {
            "metric": f"graph_3hop_{NE}edges",
            "value": round(conc_eps, 1),
            "unit": "edges/s",
            "vs_baseline": round(conc_eps / cpu_eps, 2) if cpu_eps else None,
            "p50_ms": round(p50, 1),
            "seq_edges_per_s": round(seq_eps, 1),
            "concurrent_clients": nthreads,
            "latency_ms": _pcts(conc_times),
            "dispatches_per_query": round(
                dstats["dispatches"] / max(dstats["submitted"], 1), 3
            ),
            "cpu_edges_per_s": round(cpu_eps, 1),
        }
    )
    return conc_eps / cpu_eps if cpu_eps else None


def _knn_ground_truth(corpus, queries, k):
    """Exact top-k by euclidean distance, chunked float32 BLAS."""
    n = corpus.shape[0]
    q2 = (queries**2).sum(axis=1)[:, None]
    best_d = np.full((queries.shape[0], k), np.inf, dtype=np.float64)
    best_i = np.zeros((queries.shape[0], k), dtype=np.int64)
    step = 131_072
    for i in range(0, n, step):
        blk = corpus[i : i + step]
        d = q2 + (blk**2).sum(axis=1)[None, :] - 2.0 * (queries @ blk.T)
        merged_d = np.concatenate([best_d, d], axis=1)
        merged_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(i, i + blk.shape[0]), d.shape)], axis=1
        )
        sel = np.argpartition(merged_d, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(merged_d, sel, axis=1)
        best_i = np.take_along_axis(merged_i, sel, axis=1)
    order = np.argsort(best_d, axis=1)
    return np.take_along_axis(best_i, order, axis=1)


def kick_ann_warmup(ds, s, corpus):
    """Fire one kNN query in a background thread: builds the device mirror
    and kicks background IVF training, overlapping both with the remaining
    ingest + configs so no timed section pays the training cliff."""
    import threading

    sql = "SELECT id FROM item WHERE emb <|10,64|> $q"

    def warm():
        try:
            run(ds, s, sql, {"q": corpus[0].tolist()})
        except Exception as e:  # noqa: BLE001
            log(f"ann warmup failed: {e}")

    t = threading.Thread(target=warm, daemon=True)
    t.start()
    return t


def wait_ann_ready(ds, timeout=600):
    mirror = ds.index_stores.get("bench", "bench", "item", "iemb")
    if mirror is None:
        return None
    if not mirror.wait_ivf(timeout):
        log("knn: WARNING — IVF training did not finish; exact path serves")
    return mirror


def bench_knn(ds, s, corpus, rng):
    from surrealdb_tpu import cnf

    k = 10
    nq = 24
    qidx = rng.integers(0, NI, size=nq)
    qs = corpus[qidx] + rng.standard_normal((nq, D)).astype(np.float32) * 0.05
    sql = f"SELECT id FROM item WHERE emb <|{k},64|> $q"
    queries = [(sql, {"q": qs[i].tolist()}) for i in range(nq)]

    log("knn: waiting for IVF (trained during ingest)")
    mirror = wait_ann_ready(ds)
    log("knn: IVF timed pass")
    ivf_qps, ivf_p50, results = timed_queries(ds, s, queries, warmup=1)

    log("knn: ground truth for recall")
    gt = _knn_ground_truth(corpus, qs.astype(np.float32), k)
    hits = 0
    for i, res in enumerate(results):
        got = {int(str(r["id"]).split(":")[1]) for r in res}
        hits += len(got & set(gt[i].tolist()))
    recall = hits / (nq * k)

    log("knn: concurrent-clients pass (dispatch coalescing)")
    import threading

    # untimed warm burst at the SAME client count as the timed pass:
    # compiles the batch-tile shapes the coalesced pass will hit (an XLA
    # compile mid-measurement would skew the number)
    wthreads = [
        threading.Thread(target=lambda i=i: run(ds, s, sql, {"q": qs[i % nq].tolist()}))
        for i in range(32)
    ]
    for t in wthreads:
        t.start()
    for t in wthreads:
        t.join()

    stats0 = ds.dispatch.stats()  # diff out the sequential passes
    widths0 = ds.dispatch.width_distribution()
    nthreads, rounds = 32, 2
    cq = rng.integers(0, NI, size=nthreads * rounds)
    cqs = corpus[cq] + rng.standard_normal((len(cq), D)).astype(np.float32) * 0.05
    errors = []
    conc_times = []  # per-query wall latency (list.append is GIL-atomic)
    barrier = threading.Barrier(nthreads + 1)

    def client(i):
        barrier.wait()
        for r_ in range(rounds):
            tq = time.perf_counter()
            try:
                run(ds, s, sql, {"q": cqs[i * rounds + r_].tolist()})
                conc_times.append(time.perf_counter() - tq)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(nthreads)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    conc_dt = time.perf_counter() - t0
    conc_qps = (nthreads * rounds - len(errors)) / conc_dt if conc_dt > 0 else 0.0
    if errors:
        fail(f"knn: {len(errors)} concurrent queries failed; first: {errors[0]!r:.300}")
    d1 = ds.dispatch.stats()
    dstats = {k: d1[k] - stats0[k] for k in d1}
    w1 = ds.dispatch.width_distribution()
    conc_widths = {
        str(w): n - widths0.get(w, 0) for w, n in sorted(w1.items()) if n - widths0.get(w, 0)
    }

    log("knn: exact device pass")
    saved = cnf.TPU_ANN_MIN_ROWS
    cnf.TPU_ANN_MIN_ROWS = 1 << 62  # force the exact fused kernel
    exact_qps, exact_p50, _ = timed_queries(ds, s, queries[:8], warmup=1)
    cnf.TPU_ANN_MIN_ROWS = saved

    # -- honest CPU baselines -------------------------------------------
    # (a) CPU-ANN: the engine's ivf-host strategy (same IVF, probe + exact
    #     rerank in numpy) — the sublinear competitor the 10x claim is
    #     judged against; measured sequentially AND with the same
    #     concurrency as the device pass.
    # (b) CPU exact full scan: reported for reference only.
    log("knn: cpu-ANN baseline (ivf-host)")
    cpu_mode(True)
    cpu_ann_qps, cpu_ann_p50, cres = timed_queries(ds, s, queries[:8], warmup=1)

    # fewer CPU clients than the device pass: python host search does not
    # scale with threads (GIL), so 8 un-thrashed clients give the host its
    # BEST concurrent rate — the honest comparison point
    cpu_clients = 8
    cerrors = []
    cbarrier = threading.Barrier(cpu_clients + 1)

    def cpu_client(i):
        cbarrier.wait()
        try:
            run(ds, s, sql, {"q": cqs[i * rounds].tolist()})
        except Exception as e:  # noqa: BLE001
            cerrors.append(e)

    cthreads = [threading.Thread(target=cpu_client, args=(i,)) for i in range(cpu_clients)]
    for t in cthreads:
        t.start()
    cbarrier.wait()
    t0 = time.perf_counter()
    for t in cthreads:
        t.join()
    cpu_ann_conc_qps = (cpu_clients - len(cerrors)) / (time.perf_counter() - t0)
    if cerrors:
        fail(f"knn: {len(cerrors)} concurrent ivf-host queries failed; first: {cerrors[0]!r:.300}")

    log("knn: cpu exact full scan (reference point)")
    saved_min = cnf.TPU_ANN_MIN_ROWS
    cnf.TPU_ANN_MIN_ROWS = 1 << 62  # hide IVF: force the exact host scan
    t0 = time.perf_counter()
    run(ds, s, sql, queries[0][1])
    cpu_exact_qps = 1 / (time.perf_counter() - t0)
    cnf.TPU_ANN_MIN_ROWS = saved_min
    cpu_mode(False)

    # CPU-ANN recall over the same queries (it probes the same lists, so
    # this also validates the baseline is doing comparable work)
    chits = 0
    for i, res in enumerate(cres):
        got = {int(str(r["id"]).split(":")[1]) for r in res}
        chits += len(got & set(gt[i].tolist()))
    cpu_ann_recall = chits / (len(cres) * k)

    log("knn: profiler overhead A/B (sampler live vs paused)")
    prof_overhead = _profiler_overhead(ds, s, queries[:8])
    log("knn: accounting overhead A/B (tenant meters on vs off)")
    acct_overhead = _accounting_overhead(ds, s, queries[:8])
    log("knn: advisor overhead A/B (sweeps live vs parked)")
    adv_overhead = _advisor_overhead(ds, s, queries[:8])
    log("knn: plan-cache parity (cold vs warm byte-compare)")
    pc_parity = _plan_cache_parity(ds, s, queries[:8])

    vsb = conc_qps / cpu_ann_conc_qps if cpu_ann_conc_qps else None
    emit(
        {
            "metric": f"knn_qps_recall{int(recall * 100)}_{NI}x{D}",
            "value": round(conc_qps, 2),
            "unit": "qps",
            "vs_baseline": round(vsb, 2) if vsb else None,
            "recall_at_10": round(recall, 4),
            "single_stream_qps": round(ivf_qps, 2),
            "p50_ms": round(ivf_p50, 1),
            "concurrent_clients": nthreads,
            "latency_ms": _pcts(conc_times),
            "conc_width_dist": conc_widths,
            "dispatches_per_query": round(
                dstats["dispatches"] / max(dstats["submitted"], 1), 3
            ),
            "exact_device_qps": round(exact_qps, 2),
            "exact_device_p50_ms": round(exact_p50, 1),
            "cpu_ann_qps": round(cpu_ann_qps, 2),
            "cpu_ann_conc_qps": round(cpu_ann_conc_qps, 2),
            "cpu_ann_p50_ms": round(cpu_ann_p50, 1),
            "cpu_ann_recall_at_10": round(cpu_ann_recall, 4),
            "cpu_exact_qps": round(cpu_exact_qps, 3),
            "profiler_overhead": prof_overhead,
            "accounting_overhead": acct_overhead,
            "advisor_overhead": adv_overhead,
            "plan_cache_parity": pc_parity,
        }
    )
    assert pc_parity["parity"], "plan-cache warm serve diverged from cold parse"
    return vsb, conc_qps, recall


def _profiler_overhead(ds, s, queries, rounds=3):
    """Measured cost of the always-on sampling profiler on the engine
    path (schema/12; the <=3% contract scripts/bench_gate.py enforces):
    the SAME query battery timed with the sampler live vs paused, in
    alternating paired rounds. The reported overhead takes the MINIMUM
    on/off ratio across rounds — paired minima cancel the scheduler noise
    that dwarfs a single-digit-percent effect on a 2-core container —
    clamped at 0 (a negative reading is noise, not a speedup)."""
    from surrealdb_tpu import profiler

    ratios = []
    last_on = last_off = None
    for _ in range(max(rounds, 1)):
        profiler.resume()
        t0 = time.perf_counter()
        for sql, v in queries:
            run(ds, s, sql, v)
        last_on = time.perf_counter() - t0
        profiler.pause()
        t0 = time.perf_counter()
        for sql, v in queries:
            run(ds, s, sql, v)
        last_off = time.perf_counter() - t0
        profiler.resume()
        if last_off > 0:
            ratios.append(last_on / last_off)
    best = min(ratios) if ratios else 1.0
    return {
        "rounds": len(ratios),
        "queries_per_round": len(queries),
        "on_s": round(last_on, 4) if last_on is not None else None,
        "off_s": round(last_off, 4) if last_off is not None else None,
        "overhead_pct": round(max(best - 1.0, 0.0) * 100.0, 2),
    }


def _accounting_overhead(ds, s, queries, rounds=3):
    """Measured cost of the tenant cost-attribution plane on the engine
    path (schema/13; the <=3% contract scripts/bench_gate.py enforces):
    the SAME query battery timed with accounting.charge() live vs gated
    off (cnf.TENANT_ACCOUNTING), in alternating paired rounds with the
    same paired-minimum estimator as _profiler_overhead."""
    from surrealdb_tpu import cnf as _cnf

    saved = _cnf.TENANT_ACCOUNTING
    ratios = []
    last_on = last_off = None
    try:
        for _ in range(max(rounds, 1)):
            _cnf.TENANT_ACCOUNTING = True
            t0 = time.perf_counter()
            for sql, v in queries:
                run(ds, s, sql, v)
            last_on = time.perf_counter() - t0
            _cnf.TENANT_ACCOUNTING = False
            t0 = time.perf_counter()
            for sql, v in queries:
                run(ds, s, sql, v)
            last_off = time.perf_counter() - t0
            if last_off > 0:
                ratios.append(last_on / last_off)
    finally:
        _cnf.TENANT_ACCOUNTING = saved
    best = min(ratios) if ratios else 1.0
    return {
        "rounds": len(ratios),
        "queries_per_round": len(queries),
        "on_s": round(last_on, 4) if last_on is not None else None,
        "off_s": round(last_off, 4) if last_off is not None else None,
        "overhead_pct": round(max(best - 1.0, 0.0) * 100.0, 2),
    }


def _advisor_overhead(ds, s, queries, rounds=3):
    """Measured cost of the advisor sweep service on the engine path
    (schema/14; the <=3% contract scripts/bench_gate.py enforces, same
    as the profiler and accounting planes): the SAME query battery timed
    with the sweep loop live vs parked (advisor.pause()), in alternating
    paired rounds with the paired-minimum estimator of
    _profiler_overhead. The live rounds run at a deliberately hostile
    0.25s sweep interval so the measurement actually overlaps sweeps —
    the default 5s cadence could dodge a sub-second round entirely and
    report a vacuous zero."""
    from surrealdb_tpu import advisor
    from surrealdb_tpu import cnf as _cnf

    saved = _cnf.ADVISOR_INTERVAL_SECS
    _cnf.ADVISOR_INTERVAL_SECS = 0.25
    ratios = []
    last_on = last_off = None
    try:
        for _ in range(max(rounds, 1)):
            advisor.resume()
            t0 = time.perf_counter()
            for sql, v in queries:
                run(ds, s, sql, v)
            last_on = time.perf_counter() - t0
            advisor.pause()
            t0 = time.perf_counter()
            for sql, v in queries:
                run(ds, s, sql, v)
            last_off = time.perf_counter() - t0
            if last_off > 0:
                ratios.append(last_on / last_off)
    finally:
        _cnf.ADVISOR_INTERVAL_SECS = saved
        advisor.resume()
    best = min(ratios) if ratios else 1.0
    return {
        "rounds": len(ratios),
        "queries_per_round": len(queries),
        "on_s": round(last_on, 4) if last_on is not None else None,
        "off_s": round(last_off, 4) if last_off is not None else None,
        "overhead_pct": round(max(best - 1.0, 0.0) * 100.0, 2),
    }


def _plan_cache_parity(ds, s, queries, repeats=3):
    """Schema/15 proof object for the fingerprint-keyed plan cache
    (dbs/plan_cache.py): the SAME query battery run cold (cache cleared,
    transcripts captured as the reference) then warmed (`repeats` extra
    passes install every shape past PLAN_CACHE_MIN_HITS) then re-run in
    a fresh measurement window, with every warm transcript byte-compared
    against its cold twin. `parity` is the cache's correctness contract
    MEASURED — a single stale serve flips it false and fails the
    validator — and the cold/warm pre-kernel split is what bench_gate's
    >=2x floor reads on config 2."""

    def norm(out):
        return json.dumps(
            [{"status": r["status"], "result": r["result"]} for r in out],
            sort_keys=True,
            default=str,
        )

    pc = ds.plan_cache
    # phase A: cold — capture reference transcripts with every parse
    # recording a cold pre-kernel timing. clear() drops entries but NOT
    # the window timing, so clearing before EACH query keeps a battery
    # that shares one fingerprint (config 2) from self-installing
    # mid-pass and serving its own tail warm — all len(queries) samples
    # stay genuinely cold.
    pc.clear()
    pc.reset_window()
    cold = []
    for sql, v in queries:
        pc.clear()
        cold.append(norm(run(ds, s, sql, v)))
    ws_cold = pc.window_stats()
    # phase B: warm every shape (min-hits install threshold included)
    for _ in range(max(repeats, 1)):
        for sql, v in queries:
            run(ds, s, sql, v)
    # phase C: pure-warm window — serves only, byte-compared to phase A.
    # The battery runs `repeats` times in this window so the warm average
    # sees repeats*len(queries) samples — single-pass µs timings are too
    # noisy for the gate's warm/cold ratio floor.
    pc.reset_window()
    warm = [norm(run(ds, s, sql, v)) for sql, v in queries]
    for _ in range(max(repeats, 1) - 1):
        for sql, v in queries:
            run(ds, s, sql, v)
    ws_warm = pc.window_stats()
    mismatches = sum(1 for c, w in zip(cold, warm) if c != w)
    cold_us = ws_cold["prekernel"]["cold_avg_us"]
    warm_us = ws_warm["prekernel"]["warm_avg_us"]
    return {
        "parity": mismatches == 0,
        "mismatches": mismatches,
        "queries": len(queries),
        "warm_hit_rate": ws_warm["hit_rate"],
        "warm_hits": ws_warm["hits"],
        "warm_misses": ws_warm["misses"],
        "verifies": ws_warm["verifies"],
        "prekernel_cold_us": cold_us,
        "prekernel_warm_us": warm_us,
        "speedup": round(cold_us / warm_us, 2) if cold_us and warm_us else None,
        "per_fingerprint": ws_warm["fingerprints"][:8],
    }


def _tenants_embed() -> dict:
    """The window's tenant cost-attribution snapshot for a config line
    (schema/13): per-(ns, db) meters plus the global conservation totals
    they must sum to (accounting resets per window in _acct_begin)."""
    from surrealdb_tpu import accounting

    snap = accounting.snapshot(limit=8)
    return {
        "per_tenant": snap["top"],
        "global": snap["global"],
        "count": snap["tenants"],
        "evicted": snap["evicted"],
    }


def bench_advisor_shift(ds, s, rng):
    """Config 12 (schema/14): the advisor plane under a SHIFTING workload.
    Three phases over dedicated tables — scan-heavy (repeated filtered
    ORDER/LIMIT scans over an unindexed predicate), point-lookup (record
    fetches; the scan evidence is gone), vector-heavy (kNN against a
    quantizer deliberately outgrown past needs_retrain's 1.5x ratio) —
    with stats/accounting reset at each transition so a phase is one
    observation window, and advisor sweeps driven EXPLICITLY (the
    background loop is parked) so the proposal lifecycle in the artifact
    is deterministic: `index.create` must appear in phase 1, expire
    during phase 2 (three evidence-free sweeps = the default decay), and
    `ivf.retrain` must hold in phase 3. Each phase snapshot embeds the
    statements/tenants state its evidence chains resolve against —
    scripts/check_bench_artifact.py resolves every pointer in-artifact."""
    from surrealdb_tpu import accounting, advisor, cnf, stats

    nrows = max(int(8_000 * SCALE), 1024)
    nvec = max(int(4_096 * SCALE), 512)
    d = 32
    phases: list = []

    def snap_phase(name):
        snap = advisor.snapshot(limit=20)
        phases.append({
            "phase": name,
            "proposals": snap["proposals"],
            "expired_ids": [r["id"] for r in snap["expired"]],
            "statements": stats.statements(limit=8),
            "tenants": accounting.top(limit=8),
            "sweep": snap["last_sweep"],
        })

    advisor.pause()
    try:
        # ---- phase 1: scan-heavy --------------------------------------
        log(f"advisor: phase 1 scan-heavy ({nrows} rows)")
        run(ds, s, "DEFINE TABLE advq SCHEMALESS")
        B = 4000
        for i in range(0, nrows, B):
            rows = [
                {"id": j, "val": int(j % 997), "grp": int(j % 13)}
                for j in range(i, min(i + B, nrows))
            ]
            run(ds, s, "INSERT INTO advq $rows RETURN NONE", {"rows": rows})
        scan_sql = (
            "SELECT id, val FROM advq WHERE val > 500 ORDER BY val DESC LIMIT 10"
        )
        nscan = 24
        t0 = time.perf_counter()
        for _ in range(nscan):
            run(ds, s, scan_sql)
        scan_qps = nscan / (time.perf_counter() - t0)
        advisor.sweep_once(ds)
        snap_phase("scan_heavy")

        # ---- phase 2: point-lookup ------------------------------------
        log("advisor: phase 2 point-lookup (scan evidence decays)")
        stats.reset()
        accounting.reset()
        nlook = 24
        t0 = time.perf_counter()
        for i in range(nlook):
            run(ds, s, f"SELECT * FROM advq:{(i * 37) % nrows}")
        lookup_qps = nlook / (time.perf_counter() - t0)
        for _ in range(max(cnf.ADVISOR_EXPIRE_SWEEPS, 1)):
            advisor.sweep_once(ds)
        snap_phase("point_lookup")

        # ---- phase 3: vector-heavy with a stale quantizer -------------
        log(f"advisor: phase 3 vector-heavy ({nvec} x {d}, outgrown IVF)")
        stats.reset()
        accounting.reset()
        saved_min = cnf.TPU_ANN_MIN_ROWS
        cnf.TPU_ANN_MIN_ROWS = 256
        try:
            run(
                ds, s,
                "DEFINE TABLE advitem SCHEMALESS; "
                f"DEFINE INDEX aemb ON advitem FIELDS emb HNSW "
                f"DIMENSION {d} DIST EUCLIDEAN EFC 64",
            )
            vecs = rng.standard_normal((nvec, d)).astype(np.float32)
            half = nvec // 2
            run(
                ds, s, "INSERT INTO advitem $rows RETURN NONE",
                {"rows": vec_rows(vecs[:half], range(half))},
            )
            knn_sql = "SELECT id FROM advitem WHERE emb <|5,16|> $q"
            # train the quantizer on the half corpus...
            run(ds, s, knn_sql, {"q": vecs[0].tolist()})
            m = ds.index_stores.get(s.ns, s.db, "advitem", "aemb")
            if m is not None:
                m.wait_ivf(120)
            # ...run the timed kNN load while it is READY...
            nknn = 12
            t0 = time.perf_counter()
            for i in range(nknn):
                run(ds, s, knn_sql, {"q": vecs[i % nvec].tolist()})
            knn_qps = nknn / (time.perf_counter() - t0)
            # ...then DOUBLE the corpus: size/trained_n = 2.0 > the 1.5
            # needs_retrain ratio — the stale state ivf.retrain cites.
            # NO query runs between this insert and the sweep: a kNN on a
            # stale quantizer would kick the self-retrain (ensure_ivf)
            # and the sweep would observe 'training', not 'stale'
            run(
                ds, s, "INSERT INTO advitem $rows RETURN NONE",
                {"rows": vec_rows(vecs[half:], range(half, nvec))},
            )
        finally:
            cnf.TPU_ANN_MIN_ROWS = saved_min
        advisor.sweep_once(ds)
        snap_phase("vector_heavy")
    finally:
        advisor.resume()

    kinds_seen = sorted({p["kind"] for ph in phases for p in ph["proposals"]})
    snap = advisor.snapshot(limit=20)
    emit(
        {
            "metric": f"advisor_shift_{nrows}r_{nvec}v",
            "value": float(len(kinds_seen)),
            "unit": "proposal-kinds",
            "vs_baseline": None,
            "scan_qps": round(scan_qps, 2),
            "lookup_qps": round(lookup_qps, 2),
            "knn_qps": round(knn_qps, 2),
            "proposal_kinds": kinds_seen,
            "advisor": {
                "phases": phases,
                "expired": snap["expired"],
                "sweeps": snap["sweeps"],
            },
        }
    )
    return None


def bench_bm25(ds, s, rng):
    vocab = _vocab()
    nq = 24
    # two moderately common terms per query -> large candidate sets
    pairs = [(vocab[int(a)], vocab[int(b)]) for a, b in rng.integers(10, 120, size=(nq, 2))]
    queries = [
        (
            "SELECT id, search::score(1) AS sc FROM doc "
            f"WHERE body @1@ '{a} {b}' ORDER BY sc DESC LIMIT 10",
            None,
        )
        for a, b in pairs
    ]
    qps, p50, _ = timed_queries(ds, s, queries, warmup=1)

    cpu_mode(True)
    t0 = time.perf_counter()
    for sql, v in queries[:8]:
        run(ds, s, sql, v)
    cpu_qps = 8 / (time.perf_counter() - t0)
    cpu_mode(False)

    emit(
        {
            "metric": f"bm25_top10_{ND}docs",
            "value": round(qps, 2),
            "unit": "qps",
            "vs_baseline": round(qps / cpu_qps, 2) if cpu_qps else None,
            "p50_ms": round(p50, 1),
            "cpu_qps": round(cpu_qps, 2),
        }
    )
    return qps / cpu_qps if cpu_qps else None


def bench_hybrid(ds, s, corpus, rng):
    nq = 8
    qidx = rng.integers(0, EH_REGION, size=nq)
    qs = corpus[qidx] + rng.standard_normal((nq, D)).astype(np.float32) * 0.05
    sql = (
        "SELECT id, count(->rel->item->rel->item) AS expand FROM item "
        "WHERE emb <|16,64|> $q AND flag = true"
    )
    queries = [(sql, {"q": qs[i].tolist()}) for i in range(nq)]
    qps, p50, _ = timed_queries(ds, s, queries, warmup=1)

    # phase attribution (the config-4 variance ROADMAP item): time the
    # statement's knn / +filter / +expand prefixes per query, so a
    # round-to-round swing names its phase instead of staying a mystery.
    # filter_ms/expand_ms are deltas between successive prefixes (same
    # engine path each adds one clause).
    sql_knn = "SELECT id FROM item WHERE emb <|16,64|> $q"
    sql_filt = "SELECT id FROM item WHERE emb <|16,64|> $q AND flag = true"
    t_knn, t_filt, t_full = [], [], []
    for i in range(nq):
        v = {"q": qs[i].tolist()}
        t0 = time.perf_counter(); run(ds, s, sql_knn, v); t_knn.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); run(ds, s, sql_filt, v); t_filt.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); run(ds, s, sql, v); t_full.append(time.perf_counter() - t0)

    def p50_of(ts):
        return sorted(ts)[len(ts) // 2] * 1e3

    phases = {
        "knn_ms": round(p50_of(t_knn), 2),
        "filter_ms": round(max(p50_of(t_filt) - p50_of(t_knn), 0.0), 2),
        "expand_ms": round(max(p50_of(t_full) - p50_of(t_filt), 0.0), 2),
    }

    cpu_mode(True)
    t0 = time.perf_counter()
    for sql_, v in queries[:2]:
        run(ds, s, sql_, v)
    cpu_qps = 2 / (time.perf_counter() - t0)
    cpu_mode(False)

    emit(
        {
            "metric": f"hybrid_knn_2hop_{NI}nodes",
            "value": round(qps, 2),
            "unit": "qps",
            "vs_baseline": round(qps / cpu_qps, 2) if cpu_qps else None,
            "p50_ms": round(p50, 1),
            "phases": phases,
            "cpu_qps": round(cpu_qps, 3),
        }
    )
    return qps / cpu_qps if cpu_qps else None


def bench_filtered_scan(ds, s):
    """Config 6: filtered SELECT over the mirrored item table — the
    vectorized columnar WHERE vs the per-row path on the SAME statement and
    data. Results are asserted identical; value = columnar qps,
    vs_baseline = speedup over the row path."""
    from surrealdb_tpu import cnf as _cnf

    # selective predicate (~0.25% of rows): flag cuts 4x, val < 10 cuts 100x
    sql = "SELECT VALUE id FROM item WHERE flag = true AND val < 10"
    nq = 12

    def ids(res):
        return sorted(str(x) for x in res)

    # row-path baseline first (mirror build then can't hide in the timed
    # columnar pass; the first columnar query below pays it visibly)
    saved_mirror = _cnf.COLUMN_MIRROR
    _cnf.COLUMN_MIRROR = False
    t0 = time.perf_counter()
    row_res = run(ds, s, sql)[-1]["result"]
    row_n = 3
    for _ in range(row_n - 1):
        run(ds, s, sql)
    row_qps = row_n / (time.perf_counter() - t0)
    _cnf.COLUMN_MIRROR = saved_mirror

    col_qps, col_p50, col_results = timed_queries(
        ds, s, [(sql, None) for _ in range(nq)], warmup=1
    )
    same = ids(col_results[0]) == ids(row_res)

    # count-only twin: the mask popcount path never touches a document
    csql = "SELECT count() FROM item WHERE flag = true AND val < 10 GROUP ALL"
    t0 = time.perf_counter()
    cnt = run(ds, s, csql)[-1]["result"]
    count_ms = (time.perf_counter() - t0) * 1e3

    # ---- sustained mirrored-table ingest (the v2 delta-feed headline):
    # rounds of (bulk INSERT + immediately-serving columnar SELECT) against
    # the LIVE mirror, measured with the delta feed OFF (r10 semantics:
    # every bulk op arms a full re-scan rebuild and the next query falls to
    # the row path) and ON (the delta applies at commit and the very next
    # query serves columnar). Parity is asserted every round against the
    # row path — a stale mask serving would fail loudly here.
    def sustained(delta_on, base):
        saved = _cnf.COLUMN_DELTA_FEED
        _cnf.COLUMN_DELTA_FEED = delta_on
        # batch size ~NI/40 keeps the phase query-proportional (a serving
        # table ingesting steadily), so the mirror effect is what's
        # measured rather than raw insert cost
        B, rounds = max(NI // 40, 256), 4
        q = "SELECT VALUE id FROM item WHERE flag = true AND val < 10"
        parity_fails = 0
        try:
            # start each phase from a CURRENT mirror (the r10 phase leaves
            # it stale behind its debounced rebuild window)
            ds.column_mirrors.wait_rebuild()
            ds.column_mirrors.build(ds, s.ns, s.db, "item")
            total, dt = 0, 0.0
            for rnd in range(rounds):
                rows = [
                    {"id": base + rnd * B + j, "val": 5, "flag": j % 2 == 0}
                    for j in range(B)
                ]
                t0 = time.perf_counter()
                run(ds, s, "INSERT INTO item $rows RETURN NONE", {"rows": rows})
                got = ids(run(ds, s, q)[-1]["result"])
                dt += time.perf_counter() - t0
                total += B
                # EVERY round checks the immediately-serving result against
                # the row path (outside the timed window): a stale mask
                # serving any round is a parity failure, not a slow round
                _cnf.COLUMN_MIRROR = False
                want = ids(run(ds, s, q)[-1]["result"])
                _cnf.COLUMN_MIRROR = saved_mirror
                if got != want:
                    parity_fails += 1
            return total / dt, parity_fails
        finally:
            _cnf.COLUMN_DELTA_FEED = saved
    r10_rate, pf0 = sustained(False, 10_000_000)
    v2_rate, pf1 = sustained(True, 20_000_000)
    ds.column_mirrors.wait_rebuild()  # r10-mode armed rebuilds, settle them
    # the sustained rows stay: they carry no `emb`, so every kNN-driven
    # config is blind to them, and config 6's own metrics ran above
    sustained_ratio = round(v2_rate / r10_rate, 2) if r10_rate else None

    log("filtered_scan: plan-cache parity (cold vs warm byte-compare)")
    pc_parity = _plan_cache_parity(ds, s, [(sql, None), (csql, None)])

    ratio = col_qps / row_qps if row_qps else None
    emit(
        {
            "metric": f"filtered_scan_{NI}rows",
            "value": round(col_qps, 2),
            "unit": "qps",
            "vs_baseline": round(ratio, 2) if ratio else None,
            "p50_ms": round(col_p50, 2),
            "row_path_qps": round(row_qps, 3),
            "same_results": same,
            "rows_matched": len(ids(col_results[0])),
            "count_only_ms": round(count_ms, 2),
            "count_result": cnt[0]["count"] if cnt else 0,
            "ingest": {
                "sustained_rows_s": round(v2_rate, 1),
                "r10_rows_s": round(r10_rate, 1),
                "delta_vs_r10": sustained_ratio,
                "parity_failures": pf0 + pf1,
            },
            "plan_cache_parity": pc_parity,
        }
    )
    assert pc_parity["parity"], "plan-cache warm serve diverged from cold parse"
    return ratio


def bench_ordered_agg(ds, s):
    """Config 9: the vectorized SELECT pipeline (ops/pipeline.py) — an
    ORDER BY+LIMIT statement (mask -> argsort -> top-k, late
    materialization) and a GROUP BY aggregate statement (factorize +
    segment-reduce) measured columnar vs the row-at-a-time postprocess on
    the SAME item corpus. Results asserted identical per statement; value
    = combined columnar qps, vs_baseline = combined speedup."""
    from surrealdb_tpu import cnf as _cnf, telemetry as _tm

    # ties on val resolve by scan order on both paths (stable sorts), so
    # the full sort stays on the vectorized lexsort plane
    order_sql = (
        "SELECT id, val FROM item WHERE flag = true ORDER BY val DESC LIMIT 20"
    )
    agg_sql = (
        "SELECT flag, count() AS n, math::sum(val) AS s, math::min(val) AS mn, "
        "math::max(val) AS mx, math::mean(val) AS avg "
        "FROM item WHERE val < 500 GROUP BY flag"
    )

    def norm(rows):
        return json.dumps(rows, default=repr, sort_keys=True)

    out = {}
    pushed0 = {
        k: _tm.get_counter("column_pipeline", outcome=k)
        for k in ("ordered", "grouped")
    }
    saved = _cnf.COLUMN_MIRROR
    for name, sql, nq_col, nq_row in (
        ("order", order_sql, 12, 3),
        ("agg", agg_sql, 12, 3),
    ):
        # row-path baseline first (the mirror build then can't hide inside
        # the timed columnar pass); finally-restored so a failing baseline
        # query can't leave mirrors off for every later config
        _cnf.COLUMN_MIRROR = False
        try:
            t0 = time.perf_counter()
            row_res = run(ds, s, sql)[-1]["result"]
            for _ in range(nq_row - 1):
                run(ds, s, sql)
            row_qps = nq_row / (time.perf_counter() - t0)
        finally:
            _cnf.COLUMN_MIRROR = saved
        col_qps, col_p50, col_results = timed_queries(
            ds, s, [(sql, None) for _ in range(nq_col)], warmup=1
        )
        out[name] = {
            "col_qps": round(col_qps, 2),
            "row_qps": round(row_qps, 3),
            "p50_ms": round(col_p50, 2),
            "ratio": round(col_qps / row_qps, 2) if row_qps else None,
            "same_results": norm(col_results[0]) == norm(row_res),
            "rows": len(col_results[0]),
        }
    engaged = {
        k: _tm.get_counter("column_pipeline", outcome=k) - pushed0[k]
        for k in ("ordered", "grouped")
    }
    pipeline = {
        k[0][1]: int(v)
        for k, v in _tm.counters_matching("column_pipeline").items()
    }
    ratios = [v["ratio"] for v in out.values() if v["ratio"]]
    ratio = round(min(ratios), 2) if ratios else None
    log("ordered_agg: plan-cache parity (cold vs warm byte-compare)")
    pc_parity = _plan_cache_parity(ds, s, [(order_sql, None), (agg_sql, None)])
    emit(
        {
            "metric": f"ordered_agg_{NI}rows",
            "value": out["order"]["col_qps"],
            "unit": "qps",
            "vs_baseline": ratio,
            "order": out["order"],
            "agg": out["agg"],
            "pipeline": pipeline,
            "pipeline_engaged": engaged,
            "same_results": out["order"]["same_results"] and out["agg"]["same_results"],
            "plan_cache_parity": pc_parity,
        }
    )
    assert pc_parity["parity"], "plan-cache warm serve diverged from cold parse"
    assert out["order"]["same_results"], "ordered columnar result diverged"
    assert out["agg"]["same_results"], "aggregate columnar result diverged"
    assert engaged["ordered"] > 0 and engaged["grouped"] > 0, (
        f"pipeline never engaged: {engaged}"
    )
    return ratio


def bench_cluster(rng):
    """Config 7: 2-node sharded serving (surrealdb_tpu/cluster/) over its
    own small corpus — measures coordinator kNN qps and PROVES merged-
    result parity: the cluster must return byte-identical results to a
    single node holding the same dataset for SELECT-with-WHERE, exact kNN
    top-k and BM25 (the scatter/gather executor's correctness contract).
    Self-contained: builds its own nodes, never touches the main ds."""
    import uuid as _uuid

    from surrealdb_tpu import cluster as _cluster, tracing
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.kvs.ds import Datastore
    from surrealdb_tpu.net.server import serve as _serve

    n = max(min(int(4096 * SCALE), 4096), 256)
    d = min(D, 64)  # merge mechanics, not corpus scale — keep the wire light
    s = Session.owner("bench", "bench")
    ref = Datastore("memory")
    srv1 = _serve("memory", port=0, auth_enabled=False).start_background()
    srv2 = _serve("memory", port=0, auth_enabled=False).start_background()
    nodes = [{"id": "n1", "url": srv1.url}, {"id": "n2", "url": srv2.url}]
    ds1 = srv1.httpd.RequestHandlerClass.ds
    ds2 = srv2.httpd.RequestHandlerClass.ds
    _cluster.attach(ds1, _cluster.ClusterConfig(nodes, "n1", secret="bench"))
    _cluster.attach(ds2, _cluster.ClusterConfig(nodes, "n2", secret="bench"))
    try:
        ddl = (
            "DEFINE TABLE item SCHEMALESS; "
            "DEFINE TABLE doc SCHEMALESS; "
            "DEFINE ANALYZER simple TOKENIZERS blank,class FILTERS lowercase; "
            "DEFINE INDEX fbody ON doc FIELDS body SEARCH ANALYZER simple BM25"
        )
        for target in (ref.execute, ds1.execute):
            for r in target(ddl, s):
                assert r["status"] == "OK", r
        corpus = rng.standard_normal((n, d)).astype(np.float32)
        vals = rng.random(n)
        vocab = [f"w{i}" for i in range(60)]
        from surrealdb_tpu import telemetry as _tm

        bulk_rows0 = sum(_tm.counters_matching("bulk_insert_rows").values())
        t0 = time.perf_counter()
        for lo in range(0, n, 512):
            hi = min(lo + 512, n)
            rows = [
                {
                    "id": i,
                    "emb": corpus[i].tolist(),
                    "val": float(vals[i]),
                    # int group/aggregate column: the partial-aggregate
                    # pushdown merges int sums byte-exactly (float sums
                    # refuse and fall back to the replay path)
                    "grp": i % 7,
                    # distinct tf profiles -> distinct BM25 scores, so the
                    # byte-identical comparison is order-meaningful
                    "body": " ".join(
                        vocab[int(w)] for w in rng.integers(0, 60, size=4 + i % 5)
                    ),
                }
                for i in range(lo, hi)
            ]
            for target in (ref.execute, ds1.execute):
                r = target("INSERT INTO item $rows", s, {"rows": [
                    {k: row[k] for k in ("id", "emb", "val", "grp")} for row in rows
                ]})
                assert r[0]["status"] == "OK", r
                r = target("INSERT INTO doc $rows", s, {"rows": [
                    {"id": row["id"], "body": row["body"]} for row in rows
                ]})
                assert r[0]["status"] == "OK", r
        ingest_s = time.perf_counter() - t0
        # routed-bulk proof: the coordinator's owner-grouped batches must
        # execute through try_bulk_insert ON THE REMOTE NODE (in-process
        # nodes share the telemetry registry): ref wrote 2n rows bulk and
        # the cluster wrote 2n more onto EACH of the rf replicas —
        # anything less means a shard fell back to the per-row pipeline
        from surrealdb_tpu import cnf as _cnf

        rf = max(min(_cnf.CLUSTER_RF, len(nodes)), 1)
        bulk_rows = sum(_tm.counters_matching("bulk_insert_rows").values()) - bulk_rows0
        ingest_parity = bulk_rows >= (2 + 2 * rf) * n
        spread = {}
        for name, node_ds in (("n1", ds1), ("n2", ds2)):
            c = node_ds.execute_local("SELECT count() FROM item GROUP ALL", s)
            rows_held = c[0]["result"][0]["count"] if c[0]["result"] else 0
            spread[name] = int(rows_held)
        assert sum(spread.values()) == n * rf, spread

        # ---- merged-result parity (the correctness contract)
        where_sql = "SELECT * FROM item WHERE val < 0.25"
        knn_sql = "SELECT id FROM item WHERE emb <|10|> $q"
        bm_sql = (
            "SELECT id, search::score(1) AS sc FROM doc "
            "WHERE body @1@ 'w3 w7' ORDER BY sc DESC LIMIT 10"
        )
        qv = {"q": (corpus[17] + 0.01).tolist()}
        # GROUP BY pushdown: the coordinator must merge per-shard PARTIAL
        # aggregates (cluster_agg{outcome=pushed}) instead of shipping and
        # replaying every surviving row — with byte-identical results
        agg_sql = (
            "SELECT grp, count() AS n, math::sum(grp) AS sg, "
            "math::min(grp) AS mn, math::max(grp) AS mx "
            "FROM item GROUP BY grp ORDER BY grp"
        )
        from surrealdb_tpu import telemetry as _tm2

        agg_pushed0 = _tm2.get_counter("cluster_agg", outcome="pushed")
        parity = {
            "where": ref.execute(where_sql, s)[0]["result"]
            == ds1.execute(where_sql, s)[0]["result"],
            "knn": ref.execute(knn_sql, s, dict(qv))[0]["result"]
            == ds1.execute(knn_sql, s, dict(qv))[0]["result"],
            "bm25": ref.execute(bm_sql, s)[0]["result"]
            == ds1.execute(bm_sql, s)[0]["result"],
            "agg": ref.execute(agg_sql, s)[0]["result"]
            == ds1.execute(agg_sql, s)[0]["result"],
        }
        agg_pushdown = (
            _tm2.get_counter("cluster_agg", outcome="pushed") > agg_pushed0
        )

        # ---- one request, one span tree across nodes
        tid = _uuid.uuid4().hex
        with tracing.request("bench_cluster", trace_id=tid):
            tracing.force_keep()
            ds1.execute(where_sql, s)
        doc = tracing.get_trace(tid) or {"spans": []}
        trace_nodes = sorted(
            {sp["labels"]["node"] for sp in doc["spans"] if "node" in sp["labels"]}
        )

        # ---- kNN qps through the coordinator vs the single node
        nq = 24
        qs = corpus[rng.integers(0, n, size=nq)] + 0.01
        queries = [{"q": qs[i].tolist()} for i in range(nq)]
        for target in (ds1, ref):  # warm both paths
            target.execute(knn_sql, s, dict(queries[0]))
        ds1.cluster.executor.reset_profiles()  # profile the MEASURED window
        t0 = time.perf_counter()
        for v in queries:
            r = ds1.execute(knn_sql, s, dict(v))
            assert r[0]["status"] == "OK", r
        cl_qps = nq / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        for v in queries:
            ref.execute(knn_sql, s, dict(v))
        single_qps = nq / (time.perf_counter() - t0)

        # ---- the observability plane's own evidence: the federated
        # bundle from the coordinator + the slowest statement's per-shard
        # profile (validator: shard timings must cover every live node)
        from surrealdb_tpu.cluster.federation import federated_bundle

        slowest = ds1.cluster.executor.slowest_profile()
        fed = federated_bundle(ds1, trace_limit=10, full_traces=2)
        cluster_obs = {
            "bundle": fed,
            "slowest_profile": slowest,
            "live_nodes": [nd["id"] for nd in nodes],
            # one interpreter, shared global registries: per-node sections
            # mirror one state (cluster/federation.py in-process caveat)
            "in_process": True,
        }

        emit(
            {
                "metric": f"cluster_knn_qps_2nodes_{n}x{d}",
                "value": round(cl_qps, 2),
                "unit": "qps",
                "vs_baseline": None,
                "single_node_qps": round(single_qps, 2),
                "scale_ratio": round(cl_qps / single_qps, 3) if single_qps else None,
                "ingest_s": round(ingest_s, 2),
                # the cluster ingest's own rate (2 tables x n rows through
                # the coordinator + the single-node twin, one window)
                "ingest_rate_rows_s": round(4 * n / ingest_s, 1) if ingest_s else None,
                "cluster": {
                    "nodes": len(nodes),
                    "rf": rf,
                    "per_node_rows": spread,
                    "parity": all(parity.values()),
                    "parity_detail": parity,
                    "trace_nodes": trace_nodes,
                    "ingest_bulk_path": ingest_parity,
                    "ingest_bulk_rows": int(bulk_rows),
                    "agg_pushdown": agg_pushdown,
                },
                "cluster_obs": cluster_obs,
            }
        )
        assert all(parity.values()), f"cluster parity broken: {parity}"
        assert agg_pushdown, "cluster GROUP BY never took the partial-aggregate path"
        assert ingest_parity, (
            f"cluster ingest fell off the bulk path: {bulk_rows} < {4 * n}"
        )
    finally:
        srv1.shutdown()
        srv2.shutdown()
        ds1.close()
        ds2.close()
        ref.close()
    return None  # scale-out ratio, not a vs-CPU speedup: keep out of the geomean


def bench_multi_tenant(rng):
    """Config 11: the tenant cost-attribution window — a 2-node cluster
    serving THREE namespaces (one deliberately abusive, two well-behaved)
    through one coordinator. The contracts measured: CONSERVATION (the
    per-tenant meter sums equal the independent global telemetry counters
    and dispatch-queue timers, <=1% — the validator enforces it),
    ATTRIBUTION (>=90% of the scan volume lands on the abusive namespace),
    the observe-only budget plane (the abusive tenant crosses its
    rows-scanned soft budget mid-window -> `tenant.budget_exceeded` event
    trace-linked to the offending statement) and the federated node-tagged
    `GET /tenants?cluster=1` view. Self-contained: own nodes, own corpus,
    never touches the main ds."""
    import json as _json
    from urllib.request import urlopen

    from surrealdb_tpu import accounting, cluster as _cluster, cnf as _cnf
    from surrealdb_tpu import events as _events, telemetry as _tm
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.kvs.ds import Datastore  # noqa: F401 — session twin
    from surrealdb_tpu.net.server import serve as _serve

    n = max(min(int(2048 * SCALE * 10), 2048), 256)
    d = min(D, 32)  # attribution mechanics, not corpus scale
    srv1 = _serve("memory", port=0, auth_enabled=False).start_background()
    srv2 = _serve("memory", port=0, auth_enabled=False).start_background()
    nodes = [{"id": "n1", "url": srv1.url}, {"id": "n2", "url": srv2.url}]
    ds1 = srv1.httpd.RequestHandlerClass.ds
    ds2 = srv2.httpd.RequestHandlerClass.ds
    _cluster.attach(ds1, _cluster.ClusterConfig(nodes, "n1", secret="bench"))
    _cluster.attach(ds2, _cluster.ClusterConfig(nodes, "n2", secret="bench"))
    tenants = ["acme", "globex", "abusive"]
    sessions = {ns: Session.owner(ns, "app") for ns in tenants}
    # soft budget (observe-only): the abusive tenant's scan loop must
    # cross it mid-window, so the breach event fires trace-linked
    budget_rows = float(2 * n)
    saved_budget = getattr(_cnf, "TENANT_BUDGET_ROWS", "")
    _cnf.TENANT_BUDGET_ROWS = f"abusive:{budget_rows}"
    try:
        corpus = rng.standard_normal((n, d)).astype(np.float32)
        vals = rng.random(n)
        for ns in tenants:
            s = sessions[ns]
            for r in ds1.execute("DEFINE TABLE item SCHEMALESS", s):
                assert r["status"] == "OK", r
            for lo in range(0, n, 512):
                hi = min(lo + 512, n)
                rows = [
                    {"id": i, "emb": corpus[i].tolist(), "val": float(vals[i])}
                    for i in range(lo, hi)
                ]
                r = ds1.execute("INSERT INTO item $rows", s, {"rows": rows})
                assert r[0]["status"] == "OK", r

        # ---- the measured window: meters + counters from a common zero
        accounting.reset()
        cpu0 = _tm.get_counter("statement_cpu_seconds")
        scan0 = _tm.get_counter("statement_rows_scanned")
        bg0 = _tm.get_counter("bg_task_seconds")
        disp0 = {
            nid: nds.dispatch.stats() for nid, nds in (("n1", ds1), ("n2", ds2))
        }
        scan_sql = "SELECT id FROM item WHERE val < 0.9"
        point_sqls = [f"SELECT * FROM item:{i}" for i in (17, 42)]
        stmts = 0
        t0 = time.perf_counter()
        for rnd in range(6):
            # the abusive tenant full-scans every round; the others stay
            # on record-access point reads (no table scan — an un-indexed
            # kNN here would brute-force the whole table and drown the
            # attribution signal in honest-but-identical scan volume)
            r = ds1.execute(scan_sql, sessions["abusive"])
            assert r[0]["status"] == "OK", r
            stmts += 1
            for ns in ("acme", "globex"):
                for sql in point_sqls:
                    r = ds1.execute(sql, sessions[ns])
                    assert r[0]["status"] == "OK", r
                    stmts += 1
        mix_s = time.perf_counter() - t0
        qps = stmts / mix_s if mix_s else None

        # ---- conservation: per-tenant sums vs the INDEPENDENT mirrors
        per_tenant = accounting.top(limit=100, fp_limit=4)
        sums = {
            m: sum((e.get(m) or 0.0) for e in per_tenant)
            for m in ("cpu_s", "rows_scanned", "dispatch_s", "bg_s")
        }
        d_cpu = _tm.get_counter("statement_cpu_seconds") - cpu0
        d_scan = _tm.get_counter("statement_rows_scanned") - scan0
        d_bg = _tm.get_counter("bg_task_seconds") - bg0
        d_disp = 0.0
        for nid, nds in (("n1", ds1), ("n2", ds2)):
            st1 = nds.dispatch.stats()
            d_disp += (st1["launch_s"] - disp0[nid]["launch_s"]) + (
                st1["collect_s"] - disp0[nid]["collect_s"]
            )

        def _dev_pct(tenant_sum, counter_delta):
            if counter_delta <= 1e-9 and tenant_sum <= 1e-9:
                return 0.0
            return round(
                abs(tenant_sum - counter_delta)
                / max(counter_delta, 1e-9) * 100.0,
                3,
            )

        conservation = {
            "cpu_pct": _dev_pct(sums["cpu_s"], d_cpu),
            "rows_scanned_pct": _dev_pct(sums["rows_scanned"], d_scan),
            "dispatch_pct": _dev_pct(sums["dispatch_s"], d_disp),
            "bg_pct": _dev_pct(sums["bg_s"], d_bg),
            "evicted_during_window": accounting.snapshot(limit=1)["evicted"],
        }

        # ---- attribution: the abusive tenant owns the scan volume
        bench_rows = {
            e["ns"]: (e.get("rows_scanned") or 0.0)
            for e in per_tenant if e["ns"] in tenants
        }
        total_rows = sum(bench_rows.values())
        abusive_share = (
            bench_rows.get("abusive", 0.0) / total_rows if total_rows else 0.0
        )

        # ---- the budget plane's evidence: breach event, trace-linked
        breaches = _events.snapshot(kind_prefix="tenant.budget_exceeded")
        breach = breaches[-1] if breaches else None

        # ---- federated node-tagged view through the coordinator's HTTP
        with urlopen(
            f"{srv1.url}/tenants?cluster=1&sort=rows_scanned&limit=20"
        ) as resp:
            fed = _json.loads(resp.read().decode())

        emit(
            {
                "metric": f"multi_tenant_mix_2nodes_{n}x{d}",
                "value": round(qps, 2) if qps else None,
                "unit": "qps",
                "vs_baseline": None,
                "tenant_plane": {
                    # one interpreter, shared registries: the conservation
                    # check is exactly what this regime CAN prove
                    # (cluster/federation.py in-process caveat)
                    "in_process": True,
                    "tenants": tenants,
                    "per_tenant": [
                        e for e in per_tenant if e["ns"] in tenants
                    ],
                    "conservation": conservation,
                    "abusive": {
                        "ns": "abusive",
                        "rows_share": round(abusive_share, 4),
                        "rows_scanned": bench_rows.get("abusive", 0.0),
                    },
                    "budget": {
                        "spec": {"TENANT_BUDGET_ROWS": _cnf.TENANT_BUDGET_ROWS},
                        "breach_count": len(breaches),
                        "breach": breach,
                        "breach_trace_id": (breach or {}).get("trace_id"),
                    },
                    "federated": fed[:20],
                },
            }
        )
        assert conservation["cpu_pct"] <= 1.0, conservation
        assert conservation["rows_scanned_pct"] <= 1.0, conservation
        assert conservation["dispatch_pct"] <= 1.0, conservation
        assert abusive_share >= 0.9, f"attribution too weak: {bench_rows}"
        assert breach is not None and breach.get("trace_id"), (
            f"no trace-linked budget breach: {breaches}"
        )
        assert fed and all(e.get("node") for e in fed), fed[:3]
    finally:
        _cnf.TENANT_BUDGET_ROWS = saved_budget
        srv1.shutdown()
        srv2.shutdown()
        ds1.close()
        ds2.close()
    return None  # attribution evidence, not a vs-CPU speedup: keep out of the geomean


def bench_chaos(rng):
    """Config 8: the chaos window — a 3-node replicated cluster serving a
    scan+kNN read mix while one node is KILLED mid-window. The contract
    measured: reads keep answering (failover onto replicas, `degraded`
    flag), every answer stays byte-identical to the single-node twin
    (wrong_answers MUST be 0), errors stay bounded, and recovery_s — the
    time from the kill to the next successful read — stays small. This is
    the artifact line that makes 'the cluster survives a node loss' a
    number instead of a claim."""
    from surrealdb_tpu import cluster as _cluster, cnf as _cnf
    from surrealdb_tpu import telemetry as _tm
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.kvs.ds import Datastore
    from surrealdb_tpu.net.server import serve as _serve

    n = max(min(int(2048 * SCALE), 2048), 192)
    d = min(D, 32)
    s = Session.owner("bench", "bench")
    ref = Datastore("memory")
    servers = [
        _serve("memory", port=0, auth_enabled=False).start_background()
        for _ in range(3)
    ]
    nodes = [
        {"id": f"n{i + 1}", "url": srv.url} for i, srv in enumerate(servers)
    ]
    dss = [srv.httpd.RequestHandlerClass.ds for srv in servers]
    for i, ds_ in enumerate(dss):
        _cluster.attach(ds_, _cluster.ClusterConfig(nodes, f"n{i + 1}", secret="bench"))
    rf = max(min(_cnf.CLUSTER_RF, len(nodes)), 1)
    killed_idx = 1
    killed = False
    saved_timeout = _cnf.CLUSTER_RPC_TIMEOUT_SECS
    # recovery_s is bounded by ONE rpc timeout (slow failures never retry,
    # the breaker eats the rest) — keep the window snappy
    _cnf.CLUSTER_RPC_TIMEOUT_SECS = min(saved_timeout, 2.0)
    try:
        ddl = (
            "DEFINE TABLE item SCHEMALESS; "
            f"DEFINE INDEX iemb ON item FIELDS emb MTREE DIMENSION {d}"
        )
        for target in (ref.execute, dss[0].execute):
            for r in target(ddl, s):
                assert r["status"] == "OK", r
        corpus = rng.standard_normal((n, d)).astype(np.float32)
        t_ing = time.perf_counter()
        for lo in range(0, n, 512):
            hi = min(lo + 512, n)
            rows = [
                {"id": i, "emb": corpus[i].tolist(), "val": float(i % 97)}
                for i in range(lo, hi)
            ]
            for target in (ref.execute, dss[0].execute):
                r = target("INSERT INTO item $rows RETURN NONE", s, {"rows": rows})
                assert r[0]["status"] == "OK", r
        ingest_s = time.perf_counter() - t_ing

        scan_sql = "SELECT id FROM item WHERE val < 20"
        knn_sql = "SELECT id FROM item WHERE emb <|8|> $q"
        reads = 60
        qs = corpus[rng.integers(0, n, size=reads)] + 0.01
        # ground truth from the single-node twin, precomputed so the
        # chaos window measures ONLY the cluster's behavior
        expect_scan = ref.execute(scan_sql, s)[0]["result"]
        expect_knn = [
            ref.execute(knn_sql, s, {"q": qs[i].tolist()})[0]["result"]
            for i in range(reads)
        ]
        dss[0].execute(knn_sql, s, {"q": qs[0].tolist()})  # warm the path

        fo0 = sum(_tm.counters_matching("cluster_failover_total").values())
        from surrealdb_tpu import events as _events

        ev_seq0 = _events.last_seq()  # window-scope the timeline read
        dss[0].cluster.executor.reset_profiles()
        errors = degraded = wrong = failover_reads = 0
        t_kill = recovery_s = None
        t0 = time.perf_counter()
        for i in range(reads):
            if i == reads // 2:
                log(f"chaos: killing node n{killed_idx + 1} mid-window")
                servers[killed_idx].shutdown()
                killed = True
                t_kill = time.perf_counter()
            if i % 2 == 0:
                r = dss[0].execute(knn_sql, s, {"q": qs[i].tolist()})[0]
                want = expect_knn[i]
            else:
                r = dss[0].execute(scan_sql, s)[0]
                want = expect_scan
            if r["status"] != "OK":
                errors += 1
                continue
            if r.get("degraded"):
                degraded += 1
            if t_kill is not None and recovery_s is None:
                recovery_s = time.perf_counter() - t_kill
            if r["result"] != want:
                wrong += 1
        window_s = time.perf_counter() - t0
        failover_reads = (
            sum(_tm.counters_matching("cluster_failover_total").values()) - fo0
        )
        qps = reads / window_s if window_s else 0.0

        # ---- the chaos window's structured timeline + federated evidence:
        # the bundle is captured AFTER the kill, so the dead member's
        # section shows up `unreachable` (the degraded-bundle contract in
        # the committed artifact), and the events accounting is what
        # bench_gate floors (>=1 breaker event, 0 unattributed degraded
        # reads — a failover nobody can join to a statement)
        window_events = _events.since(ev_seq0)
        degraded_evs = [
            e for e in window_events if e["kind"] == "cluster.degraded_read"
        ]
        events_acct = {
            "total": len(window_events),
            "breaker": sum(
                1 for e in window_events if e["kind"] == "cluster.breaker_open"
            ),
            "flaps": sum(
                1 for e in window_events if e["kind"] == "cluster.node_down"
            ),
            "degraded_reads": len(degraded_evs),
            "unattributed_degraded_reads": sum(
                1 for e in degraded_evs if not e.get("trace_id")
            ),
        }
        from surrealdb_tpu.cluster.federation import federated_bundle

        live_nodes = [
            nd["id"] for i, nd in enumerate(nodes)
            if not (killed and i == killed_idx)
        ]
        cluster_obs = {
            "bundle": federated_bundle(dss[0], trace_limit=10, full_traces=2),
            "slowest_profile": dss[0].cluster.executor.slowest_profile(),
            "live_nodes": live_nodes,
            "in_process": True,  # shared registries; see federation.py caveat
        }

        emit(
            {
                "metric": f"chaos_reads_3nodes_rf{rf}_{n}x{d}",
                "value": round(qps, 2),
                "unit": "qps",
                "vs_baseline": None,
                "window_s": round(window_s, 2),
                # this config's own bulk loads (single-node twin + the
                # replicated cluster write path, one window)
                "ingest_rate_rows_s": round((1 + rf) * n / ingest_s, 1)
                if ingest_s
                else None,
                "chaos": {
                    "nodes": len(nodes),
                    "rf": rf,
                    "killed_node": f"n{killed_idx + 1}",
                    "reads": reads,
                    "failover_reads": int(failover_reads),
                    "degraded_responses": degraded,
                    "errors": errors,
                    "wrong_answers": wrong,
                    "recovery_s": round(recovery_s, 3) if recovery_s is not None else None,
                },
                "events": events_acct,
                "cluster_obs": cluster_obs,
            }
        )
        assert wrong == 0, f"chaos window produced {wrong} wrong answers"
        assert rf < 2 or degraded > 0, "node kill produced no degraded reads"
    finally:
        _cnf.CLUSTER_RPC_TIMEOUT_SECS = saved_timeout
        for i, srv in enumerate(servers):
            if not (killed and i == killed_idx):
                srv.shutdown()
        for ds_ in dss:
            ds_.close()
        ref.close()
    return None  # a survival property, not a vs-CPU speedup


def bench_elastic(rng):
    """Config 10: the elastic-chaos window — a 3-node RF=2 cluster serving
    a read mix while one node is KILLED mid-window and a REPLACEMENT joins
    (epoch bump + background shard migration over the CBOR channel), then
    anti-entropy sweeps run to convergence. The contract measured: zero
    wrong answers, zero lost acked writes, migration actually streamed
    rows, and repair time (kill -> replacement converged) stays bounded.
    This is the artifact line that makes 'capacity changes without
    downtime' a number instead of a claim."""
    from surrealdb_tpu import cluster as _cluster, cnf as _cnf
    from surrealdb_tpu import events as _events
    from surrealdb_tpu import telemetry as _tm
    from surrealdb_tpu.cluster import membership as _mship, repair as _repair
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.kvs.ds import Datastore
    from surrealdb_tpu.net.server import serve as _serve

    n = max(min(int(1024 * SCALE), 1024), 128)
    s = Session.owner("bench", "bench")
    ref = Datastore("memory")
    servers = [
        _serve("memory", port=0, auth_enabled=False).start_background()
        for _ in range(3)
    ]
    nodes = [
        {"id": f"n{i + 1}", "url": srv.url} for i, srv in enumerate(servers)
    ]
    dss = [srv.httpd.RequestHandlerClass.ds for srv in servers]
    for i, ds_ in enumerate(dss):
        _cluster.attach(ds_, _cluster.ClusterConfig(nodes, f"n{i + 1}", secret="bench"))
    rf = max(min(_cnf.CLUSTER_RF, len(nodes)), 1)
    killed_idx = 1
    killed = False
    srv4 = None
    saved_timeout = _cnf.CLUSTER_RPC_TIMEOUT_SECS
    _cnf.CLUSTER_RPC_TIMEOUT_SECS = min(saved_timeout, 2.0)
    try:
        ddl = "DEFINE TABLE item SCHEMALESS"
        for target in (ref.execute, dss[0].execute):
            for r in target(ddl, s):
                assert r["status"] == "OK", r
        t_ing = time.perf_counter()
        for lo in range(0, n, 256):
            hi = min(lo + 256, n)
            rows = [{"id": i, "val": float(i % 97)} for i in range(lo, hi)]
            for target in (ref.execute, dss[0].execute):
                r = target("INSERT INTO item $rows RETURN NONE", s, {"rows": rows})
                assert r[0]["status"] == "OK", r
        ingest_s = time.perf_counter() - t_ing

        scan_sql = "SELECT id FROM item WHERE val < 20"
        reads = 48
        expect_scan = ref.execute(scan_sql, s)[0]["result"]
        dss[0].execute(scan_sql, s)  # warm the path

        mig0 = sum(_tm.counters_matching("cluster_migration_rows").values())
        rep0 = sum(_tm.counters_matching("cluster_repair_applied_total").values())
        ae0 = sum(
            _tm.counters_matching("cluster_antientropy_repaired_total").values()
        )
        ev_seq0 = _events.last_seq()
        dss[0].cluster.executor.reset_profiles()
        errors = degraded = wrong = 0
        acked: list = []  # ids of writes acked AFTER the kill
        t_kill = None
        change = None
        joined = False
        t0 = time.perf_counter()
        for i in range(reads):
            if i == reads // 3:
                log(f"elastic: killing node n{killed_idx + 1} mid-window")
                servers[killed_idx].shutdown()
                killed = True
                t_kill = time.perf_counter()
            if i == reads // 2:
                log("elastic: joining replacement n4 mid-window")
                srv4 = _serve("memory", port=0, auth_enabled=False).start_background()
                ds4 = srv4.httpd.RequestHandlerClass.ds
                node4 = {"id": "n4", "url": srv4.url}
                _cluster.attach(
                    ds4,
                    _cluster.ClusterConfig(
                        [nodes[0], nodes[2], node4], "n4", secret="bench"
                    ),
                )
                # background migration: the window keeps reading while the
                # moving ranges stream (dual-read covers the handoff)
                change = _mship.replace(dss[0], "n2", node4, wait=False)
                joined = True
            if killed and i % 3 == 0:
                # an acked write while degraded/migrating: must survive
                wid = 10_000 + i
                for target in (ref.execute, dss[0].execute):
                    r = target(
                        f"CREATE item:{wid} SET val = 5.0", s
                    )
                    assert r[0]["status"] == "OK", r
                acked.append(wid)
                expect_scan = ref.execute(scan_sql, s)[0]["result"]
            r = dss[0].execute(scan_sql, s)[0]
            if r["status"] != "OK":
                errors += 1
                continue
            if r.get("degraded"):
                degraded += 1
            if r["result"] != expect_scan:
                wrong += 1
        window_s = time.perf_counter() - t0
        qps = reads / window_s if window_s else 0.0

        # migration must complete, then anti-entropy sweeps run to a clean
        # pass — repair_s is kill -> converged
        assert change is not None
        change.wait(120)
        sweeps = 0
        for _ in range(4):
            sweeps += 1
            reports = [
                _repair.sweep_once(d)
                for d in (dss[0], dss[2], srv4.httpd.RequestHandlerClass.ds)
            ]
            if all(r["repaired"] == 0 and not r["errors"] for r in reports):
                break
        repair_s = time.perf_counter() - t_kill if t_kill is not None else None

        # zero lost acked writes: every write acked after the kill reads
        # back through the post-cutover cluster
        lost = 0
        for wid in acked:
            got = dss[0].execute(f"SELECT VALUE val FROM item:{wid}", s)[0]
            if got["status"] != "OK" or got["result"] != [5.0]:
                lost += 1
        migration_rows = (
            sum(_tm.counters_matching("cluster_migration_rows").values()) - mig0
        )
        repaired = (
            sum(_tm.counters_matching("cluster_repair_applied_total").values())
            - rep0
        )
        antientropy = (
            sum(_tm.counters_matching("cluster_antientropy_repaired_total").values())
            - ae0
        )
        epoch = dss[0].cluster.membership.epoch

        window_events = _events.since(ev_seq0)
        events_acct = {
            "total": len(window_events),
            "member_join": sum(
                1 for e in window_events if e["kind"] == "cluster.member_join"
            ),
            "member_leave": sum(
                1 for e in window_events if e["kind"] == "cluster.member_leave"
            ),
            "migration_done": sum(
                1 for e in window_events if e["kind"] == "cluster.migration_done"
            ),
            "breaker": sum(
                1 for e in window_events if e["kind"] == "cluster.breaker_open"
            ),
        }
        from surrealdb_tpu.cluster.federation import federated_bundle

        live_nodes = ["n1", "n3", "n4"]
        # the slowest WINDOW profile predates the join (the kill's timeout
        # read) — re-profile on the post-cutover membership so the embedded
        # evidence attributes time to every live node incl. the replacement
        dss[0].cluster.executor.reset_profiles()
        for _ in range(3):
            r = dss[0].execute(scan_sql, s)[0]
            assert r["status"] == "OK", r
        cluster_obs = {
            "bundle": federated_bundle(dss[0], trace_limit=10, full_traces=2),
            "slowest_profile": dss[0].cluster.executor.slowest_profile(),
            "live_nodes": live_nodes,
            "in_process": True,  # shared registries; see federation.py caveat
        }
        emit(
            {
                "metric": f"elastic_reads_3nodes_rf{rf}_{n}",
                "value": round(qps, 2),
                "unit": "qps",
                "vs_baseline": None,
                "window_s": round(window_s, 2),
                "ingest_rate_rows_s": round((1 + rf) * n / ingest_s, 1)
                if ingest_s
                else None,
                "elastic": {
                    "nodes": len(nodes),
                    "rf": rf,
                    "killed_node": f"n{killed_idx + 1}",
                    "joined_node": "n4",
                    "epoch": epoch,
                    "reads": reads,
                    "degraded_responses": degraded,
                    "errors": errors,
                    "wrong_answers": wrong,
                    "acked_writes": len(acked),
                    "lost_acked_writes": lost,
                    "migration_rows": int(migration_rows),
                    "repaired": int(repaired),
                    "antientropy_repaired": int(antientropy),
                    "repair_sweeps": sweeps,
                    "repair_s": round(repair_s, 3) if repair_s is not None else None,
                },
                "events": events_acct,
                "cluster_obs": cluster_obs,
            }
        )
        assert wrong == 0, f"elastic window produced {wrong} wrong answers"
        assert lost == 0, f"elastic window lost {lost} acked writes"
        assert migration_rows > 0, "replacement join streamed no rows"
        assert epoch == 2, f"membership epoch {epoch} != 2 after the replace"
    finally:
        _cnf.CLUSTER_RPC_TIMEOUT_SECS = saved_timeout
        for i, srv in enumerate(servers):
            if not (killed and i == killed_idx):
                srv.shutdown()
        if srv4 is not None:
            ds4 = srv4.httpd.RequestHandlerClass.ds
            srv4.shutdown()
            ds4.close()
        for ds_ in dss:
            ds_.close()
        ref.close()
    return None  # a survival property, not a vs-CPU speedup


def bench_c1m_net():
    """Config 13 (schema/16): the C1M network plane at connection scale.

    Three phases against a dedicated event-loop server (its own Datastore;
    the corpus configs are irrelevant to ingress):
      1. idle scale   — attach >= 20k in-memory connections (the loop's
         virtual-conn path: the full ingress state machine minus the
         kernel socket, because the container's hard RLIMIT_NOFILE caps
         real fds at 20000) and measure per-connection memory under
         tracemalloc.
      2. active burst — >= 2k further connections each complete one HTTP
         /sql request with the idle herd still attached; zero errors is a
         validator rule, and the loop's own TTFB ring yields
         accept-to-first-byte p50/p99.
      3. QoS isolation — a victim tenant's fixed battery timed SOLO, then
         again while an abusive tenant floods through a deliberately
         tight quota (inflight 4, admission queue 8): the abuser's
         overflow must be shed (counted 503s), and the victim's
         contended p99 must stay within bench_gate's 3x-of-solo ceiling.
    """
    import threading
    import tracemalloc

    from surrealdb_tpu import cnf as _cnf
    from surrealdb_tpu.net import qos as _qos
    from surrealdb_tpu.net.server import serve

    IDLE_N = 20_000
    ACTIVE_N = 2_000
    ACTIVE_TENANTS = 32  # spread: per-tenant load stays under default quotas

    def req(body: str, ns: str) -> bytes:
        payload = body.encode()
        return (
            f"POST /sql HTTP/1.1\r\nHost: bench\r\nsurreal-ns: {ns}\r\n"
            f"surreal-db: app\r\nContent-Length: {len(payload)}\r\n\r\n"
        ).encode() + payload

    _qos.reset()
    srv = serve(auth_enabled=False, port=0).start_background()
    if not srv.loop_mode:
        raise RuntimeError("c1m_net needs the event-loop ingress (SURREAL_NET_LOOP)")
    loops = srv.netloop.loops
    saved = (_cnf.NET_TENANT_INFLIGHT, _cnf.NET_ADMIT_QUEUE, _cnf.NET_TENANT_RATE)
    try:
        # ---- phase 1: idle connection scale + per-conn memory ----------
        log(f"c1m_net: attaching {IDLE_N} idle connections (tracemalloc)")
        tracemalloc.start()
        m0, _ = tracemalloc.get_traced_memory()
        idle = [loops[i % len(loops)].attach_virtual() for i in range(IDLE_N)]
        m1, _ = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        per_conn_bytes = (m1 - m0) / IDLE_N

        # ---- phase 2: active burst over the idle herd ------------------
        log(f"c1m_net: active burst of {ACTIVE_N} connections")
        active = [loops[i % len(loops)].attach_virtual() for i in range(ACTIVE_N)]
        bufs = [b""] * ACTIVE_N
        t0 = time.perf_counter()
        for i, vc in enumerate(active):
            vc.feed(req("RETURN 1;", f"ns{i % ACTIVE_TENANTS}"))
        pending = set(range(ACTIVE_N))
        deadline = time.time() + 180
        while pending and time.time() < deadline:
            for i in list(pending):
                bufs[i] += active[i].take_output()
                if b"HTTP/1.1 " in bufs[i]:
                    pending.discard(i)
            if pending:
                time.sleep(0.002)
        active_dt = time.perf_counter() - t0
        errors = len(pending) + sum(
            1 for i, b in enumerate(bufs) if i not in pending and b"HTTP/1.1 200" not in b
        )
        peak_conns = srv.netloop.total_conns()
        ttfb = srv.netloop.ttfb_quantiles()
        qos_after_active = _qos.snapshot()

        # ---- phase 3: victim battery solo vs under an abusive tenant ---
        def battery(vc, ns, n):
            buf, times = b"", []
            for j in range(n):
                tq = time.perf_counter()
                # a deterministic 2ms work floor: the isolation ratio then
                # measures scheduling, not the noise floor of a no-op
                vc.feed(req("RETURN sleep(2ms) OR 9;", ns))
                while buf.count(b"HTTP/1.1 ") <= j:
                    buf += vc.take_output()
                    if time.perf_counter() - tq > 30:
                        raise RuntimeError(f"victim request {j} stalled")
                    time.sleep(0.0002)
                times.append(time.perf_counter() - tq)
            return times

        log("c1m_net: victim battery solo")
        solo = battery(loops[0].attach_virtual(), "victim", 100)

        log("c1m_net: victim battery under abusive-tenant flood")
        _cnf.NET_TENANT_INFLIGHT, _cnf.NET_ADMIT_QUEUE = 4, 8
        stop = threading.Event()
        abuse_fed = [0]
        aconns = [loops[i % len(loops)].attach_virtual() for i in range(24)]

        def abuse():
            while not stop.is_set():
                for vc in aconns:
                    vc.feed(req("RETURN sleep(10ms) OR 1;", "abuser"))
                    abuse_fed[0] += 1
                stop.wait(0.01)

        flood = threading.Thread(target=abuse)
        flood.start()
        time.sleep(0.3)  # let the flood saturate its quota + queue first
        try:
            contended = battery(loops[0].attach_virtual(), "victim", 100)
        finally:
            stop.set()
            flood.join()

        qos_final = _qos.snapshot()
        by_tenant = {(t["ns"], t["db"]): t for t in qos_final["top"]}
        abuser = by_tenant.get(("abuser", "app"), {})
        victim = by_tenant.get(("victim", "app"), {})
        solo_p = _pcts(solo)
        cont_p = _pcts(contended)
        ratio = (
            round(cont_p["p99"] / solo_p["p99"], 2)
            if solo_p["p99"] and cont_p["p99"]
            else None
        )
        emit(
            {
                "metric": f"c1m_net_{IDLE_N + ACTIVE_N}conns",
                "value": round(ACTIVE_N / active_dt, 1),
                "unit": "req/s",
                "vs_baseline": None,
                "net": {
                    "loops": len(loops),
                    "idle_conns": IDLE_N,
                    "active_conns": ACTIVE_N,
                    "peak_open_conns": peak_conns,
                    "errors": errors,
                    "per_conn_bytes": round(per_conn_bytes, 1),
                    "accept_to_first_byte": ttfb,
                    "active_qos": {
                        "admitted": qos_after_active["totals"]["admitted"],
                        "shed": qos_after_active["totals"]["shed"],
                    },
                    "victim": {
                        "solo_ms": solo_p,
                        "contended_ms": cont_p,
                        "p99_ratio": ratio,
                        "admitted": victim.get("admitted"),
                        "shed": victim.get("shed", 0),
                    },
                    "abuser": {
                        "fed": abuse_fed[0],
                        "admitted": abuser.get("admitted", 0),
                        "shed": abuser.get("shed", 0),
                        "throttled": abuser.get("throttled", 0),
                    },
                    "qos_totals": qos_final["totals"],
                },
            }
        )
        del idle, active, aconns
        return None
    finally:
        _cnf.NET_TENANT_INFLIGHT, _cnf.NET_ADMIT_QUEUE, _cnf.NET_TENANT_RATE = saved
        srv.shutdown()
        _qos.reset()


def bench_ml_scan(ds, s, rng):
    from surrealdb_tpu.ml.exec import import_model

    w = rng.standard_normal((D, 1)).astype(np.float32)
    spec = {
        "format": "linear",
        "layers": [{"w": w.tolist(), "b": [0.0], "activation": None}],
    }
    run(ds, s, "DEFINE MODEL ml::scorer<1>")
    import_model(ds, s, "scorer", "1", spec)
    # VALUE-mode single ml:: call over the indexed field rides the columnar
    # fast path: the feature column is already device-resident in the
    # vector mirror, so the whole scan is ONE forward dispatch
    sql = "SELECT VALUE ml::scorer<1>(emb) FROM item"

    run(ds, s, sql)  # warmup: compile the batched forward
    t0 = time.perf_counter()
    run(ds, s, sql)
    dt = time.perf_counter() - t0
    rows_s = NI / dt

    cpu_mode(True)
    t0 = time.perf_counter()
    run(ds, s, sql)
    cpu_rows_s = NI / (time.perf_counter() - t0)
    cpu_mode(False)

    emit(
        {
            "metric": f"ml_scan_{NI}rows",
            "value": round(rows_s, 1),
            "unit": "rows/s",
            "vs_baseline": round(rows_s / cpu_rows_s, 2) if cpu_rows_s else None,
            "scan_s": round(dt, 2),
            "cpu_rows_per_s": round(cpu_rows_s, 1),
        }
    )
    return rows_s / cpu_rows_s if cpu_rows_s else None


# ------------------------------------------------------------------ main
def main() -> None:
    from surrealdb_tpu import telemetry
    from surrealdb_tpu.kvs.ds import Datastore
    from surrealdb_tpu.dbs.session import Session

    from surrealdb_tpu import cnf as _cnf

    # every bench query's trace must be retrievable when its config's
    # accounting window closes (the slowest_trace artifact field); the
    # store bound still caps memory per window — if a window ever fills
    # it anyway, _acct_delta flags the line as trace_window_truncated
    # rather than silently reporting the slowest SURVIVOR as the slowest
    _cnf.TRACE_SAMPLE = 1.0
    _cnf.TRACE_STORE_SIZE = max(_cnf.TRACE_STORE_SIZE, 4096)

    trace_dir = os.path.join(os.path.dirname(OUT_PATH) or ".", f"bench_trace_{ROUND}")
    traces: list = []  # per-config capture dirs actually written
    if PROFILE:
        telemetry.enable(True)

    from surrealdb_tpu import device

    device.configure_compile_cache()  # before the first compile (the rtt jit)
    rtt = measure_rtt()
    log(f"device dispatch rtt: {rtt * 1e3:.1f} ms; scale={SCALE} configs={sorted(CONFIGS)}")

    ds = Datastore("memory")
    s = Session.owner()
    s.ns, s.db = "bench", "bench"
    rng = np.random.default_rng(7)

    ratios = []
    knn_qps, knn_recall = None, None
    state = {"corpus": None, "warm": None}

    # Schedule: least-measured configs first, each config's ingest lazily
    # right before it, and IVF training overlapped with ingest/configs that
    # do not need it (kicked right after the item corpus lands).
    def need_corpus():
        if state["corpus"] is None:
            state["corpus"] = gen_corpus(NI, D)
            ingest_items(ds, s, state["corpus"])
            state["warm"] = kick_ann_warmup(ds, s, state["corpus"])
        return state["corpus"]

    def run_cfg(cfg, fn):
        nonlocal knn_qps, knn_recall
        global _DEFER
        log(f"config {cfg} start")
        if PROFILE:
            # one bounded trace per config (a whole-run capture including
            # ingest produces multi-100MB traces); each config's measured
            # section lands in its own subdir
            cfg_dir = os.path.join(trace_dir, f"cfg{cfg}")
            telemetry.start_trace(cfg_dir)  # raises when it cannot start
            traces.append(cfg_dir)
            log(f"profiler: jax trace capturing into {cfg_dir}")
        # the warmup thread's one kNN query must not leak into this config's
        # accounting window (background IVF training can't be joined without
        # serializing the schedule — any overlap lands STRUCTURALLY in the
        # window's bg_tasks accounting via the flight recorder)
        if state["warm"] is not None and state["warm"].is_alive():
            state["warm"].join(timeout=120)
        acct0 = _acct_begin(ds)
        n0 = len(RESULTS)
        _DEFER = True  # buffer this config's lines so they print enriched
        try:
            r = fn()
            if cfg == "2":
                r, knn_qps, knn_recall = r
            if r:
                ratios.append(r)
        except Exception as e:  # one config failing must not kill the rest
            import traceback

            traceback.print_exc(file=sys.stderr)
            emit({"metric": f"config{cfg}", "value": None, "unit": "error", "vs_baseline": None, "error": str(e)[:200]})
            fail(f"config {cfg}: {e!r:.300}")
        finally:
            _DEFER = False
            acct = _acct_delta(ds, acct0)
            for e in acct.pop("_slow_entries"):
                log(
                    f"slow statement ({e.get('duration_s', 0):.3f}s): "
                    f"{str(e.get('sql', ''))[:200]}"
                )
            for i, line in enumerate(RESULTS[n0:]):
                line["config"] = cfg
                # run-cumulative bulk-load throughput up to this config
                # (schema/7): the gate floors it so ingest regressions
                # can't hide in setup time
                line.setdefault("ingest_rate_rows_s", ingest_rate())
                line.update(acct)
                if i > 0:
                    # the span tree is per-CONFIG evidence: carry it once,
                    # not duplicated into every metric line of the window
                    line["slowest_trace"] = None
                print(json.dumps(line), flush=True)
            if PROFILE:
                telemetry.stop_trace()
        log(f"config {cfg} done")

    if "3" in CONFIGS:
        ingest_docs(ds, s, rng)
        run_cfg("3", lambda: bench_bm25(ds, s, rng))
    if CONFIGS & {"2", "4", "5", "6", "9"}:
        need_corpus()
    if "7" in CONFIGS:
        run_cfg("7", lambda: bench_cluster(rng))
    if "8" in CONFIGS:
        run_cfg("8", lambda: bench_chaos(rng))
    if "10" in CONFIGS:
        run_cfg("10", lambda: bench_elastic(rng))
    if "11" in CONFIGS:
        run_cfg("11", lambda: bench_multi_tenant(rng))
    if "12" in CONFIGS:
        run_cfg("12", lambda: bench_advisor_shift(ds, s, rng))
    if "5" in CONFIGS:
        run_cfg("5", lambda: bench_ml_scan(ds, s, rng))
    if "6" in CONFIGS:
        run_cfg("6", lambda: bench_filtered_scan(ds, s))
    if "9" in CONFIGS:
        run_cfg("9", lambda: bench_ordered_agg(ds, s))
    if "13" in CONFIGS:
        # after at least one corpus ingest so the line's run-cumulative
        # ingest_rate_rows_s stays a positive schema/7 fact
        need_corpus()
        run_cfg("13", lambda: bench_c1m_net())
    if "4" in CONFIGS:
        ingest_hybrid_edges(ds, s, rng)
        wait_ann_ready(ds)
        run_cfg("4", lambda: bench_hybrid(ds, s, state["corpus"], rng))
    if "2" in CONFIGS:
        run_cfg("2", lambda: bench_knn(ds, s, state["corpus"], rng))
    if "1" in CONFIGS:
        ingest_person_graph(ds, s, rng)
        run_cfg("1", lambda: bench_graph_3hop(ds, s, rng))

    geo = math.exp(sum(math.log(r) for r in ratios) / len(ratios)) if ratios else None
    emit(
        {
            "metric": f"north_star_knn_qps_recall{int((knn_recall or 0) * 100)}_{NI}x{D}"
            if knn_qps is not None
            else "north_star",
            "value": round(knn_qps, 2) if knn_qps is not None else None,
            "unit": "qps",
            "vs_baseline": round(geo, 2) if geo else None,
            "rtt_ms": round(rtt * 1e3, 1),
            "configs": len(ratios),
        }
    )

    if PROFILE:
        log(f"profiler: {len(traces)} trace(s) under {trace_dir}")
        if not traces:
            fail("--profile was asked for and no trace was captured")

    # ---- driver-proof evidence: replay the full block, write + validate the
    # artifact (a truncated stdout tail still carries every config line, and
    # the JSON artifact survives even a fully lost stdout)
    print("=== bench emit block (full replay) ===", flush=True)
    for line in RESULTS:
        print(json.dumps(line), flush=True)
    from surrealdb_tpu.bundle import debug_bundle

    artifact = {
        "schema": SCHEMA,
        "round": ROUND,
        "scale": SCALE,
        "configs": sorted(CONFIGS),
        "rtt_ms": round(rtt * 1e3, 1),
        "profile_trace": trace_dir if traces else None,
        "results": RESULTS,
        # the engine state that produced these numbers — task registry,
        # compile log, mirror staleness, dispatch counters (bundle.py)
        "bundle": debug_bundle(ds),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")
    log(f"artifact written: {OUT_PATH}")

    import subprocess

    check = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts", "check_bench_artifact.py"
    )
    rc = subprocess.call([sys.executable, check, OUT_PATH])
    if rc == 0:
        log("artifact validator: OK")
    else:
        fail(f"artifact validator rejected {OUT_PATH} (rc={rc})")
    if FAILURES:
        log(f"{len(FAILURES)} failure(s): " + "; ".join(FAILURES))
        sys.exit(1)


if __name__ == "__main__":
    main()
