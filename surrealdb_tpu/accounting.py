"""Tenant cost-attribution plane: per-(ns, db) resource meters.

The engine observes everything per-statement-shape (stats.py) and
per-node (cluster/federation.py), but nothing rolls cost up to the
TENANT — so "one abusive namespace throttles that namespace, not the
node" was unmeasurable. This module is the missing rollup: a bounded
hierarchical meter store keyed by ``(ns, db)`` with per-fingerprint
drill-down, accumulated through ONE write door, :func:`charge`
(graftlint GL013 enforces the door — no other module pokes the store).

What gets charged, and where:

- **CPU + wall time, rows, bytes** — ``dbs/executor.py`` wraps every
  statement in a thread-time delta and flushes ONE charge at statement
  end (rows scanned ride a thread-local tally the iterator feeds);
- **device-dispatch occupancy + queue wait** — ``dbs/dispatch.py``
  charges every rider of a coalesced batch its own queue wait plus an
  equal share of the batch's launch/collect time, so per-tenant
  ``dispatch_s`` sums EXACTLY to the global ``launch_s + collect_s``
  counters (conservation by construction; retry re-executions are
  segregated into the non-conserved ``dispatch_retry_s``);
- **bg-task time** — ``bg.py`` charges a finished task's duration to
  the tenant whose statement ARMED it (the same parent link its
  ``trace_id`` rides);
- **cluster scatter cost** — the coordinator charges per-shard RPC
  time with a per-node breakdown (``cluster/executor.py``).

Surfaces: system-gated ``GET /tenants`` (``?cluster=1`` federates
node-tagged member stores), the debug bundle's ``tenants`` section
and ``INFO FOR ROOT``.

Budgets are observe-only:
``SURREAL_TENANT_BUDGET_{CPU_S,DISPATCH_S,ROWS,BYTES}`` define soft
limits — a plain float applies to every tenant, ``ns:limit[,ns:limit]``
per namespace. A meter crossing its limit FROM BELOW emits one
``tenant.budget_exceeded`` event (trace-linked to the crossing
statement, kept resolvable via force_keep) and bumps
``tenant_budget_breaches{ns}`` — never enforcement.

Lock discipline: ``accounting.store`` is a leaf in locks.HIERARCHY
(mutate-and-release); events/telemetry side effects are emitted AFTER
release — their locks sit at LOWER levels and must never nest inside.
"""

from __future__ import annotations

import contextvars
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from surrealdb_tpu.utils import locks as _locks

# the meter catalog: every key charge() accepts. Seconds are floats;
# counts are accumulated as floats too (one type, easy diffing).
METERS = (
    "statements",        # statements executed for this tenant
    "errors",            # statements that returned ERR
    "slow",              # statements past SLOW_QUERY_THRESHOLD_SECS
    "exec_s",            # wall-clock statement time
    "cpu_s",             # thread-CPU time (thread_time delta around execute)
    "dispatch_s",        # device launch+collect occupancy (batch share)
    "dispatch_wait_s",   # queue wait before this tenant's dispatches ran
    "dispatch_retry_s",  # split/retry re-execution time (NOT conserved —
                         # re-runs are extra device time outside launch_s)
    "dispatch_batches",  # dispatches this tenant rode (leader or follower)
    "rows_scanned",      # rows the iterator touched on this tenant's behalf
    "rows_returned",     # result rows handed back
    "rows_written",      # ingest rows (bulk_insert path)
    "bytes_in",          # HTTP request-body bytes
    "bytes_out",         # HTTP response-body bytes
    "bg_s",              # background-task time armed by this tenant
    "bg_tasks",          # background tasks armed by this tenant
    "scatter_rpc_s",     # coordinator-side cluster scatter RPC time
    "scatter_calls",     # scatter RPC attempts
    "admission_wait_s",  # coordinator admission-control queue wait
)

# meter -> cnf knob holding its soft-budget spec (observe-only)
_BUDGET_KNOBS = {
    "cpu_s": "TENANT_BUDGET_CPU_S",
    "dispatch_s": "TENANT_BUDGET_DISPATCH_S",
    "rows_scanned": "TENANT_BUDGET_ROWS",
    "bytes_out": "TENANT_BUDGET_BYTES",
}

_SORT_KEYS = frozenset(METERS)


class _Entry:
    """One tenant's accumulated meters + drill-downs."""

    __slots__ = (
        "ns", "db", "meters", "by_fp", "by_node", "bg_kinds", "breaches",
        "first_ts", "last_ts",
    )

    def __init__(self, ns: str, db: str):
        self.ns = ns
        self.db = db
        self.meters: Dict[str, float] = {}
        # fingerprint -> meters (bounded LRU, cap cnf.TENANT_FP_CAP)
        self.by_fp: "OrderedDict[str, Dict[str, float]]" = OrderedDict()
        self.by_node: Dict[str, Dict[str, float]] = {}
        self.bg_kinds: Dict[str, float] = {}
        self.breaches: Dict[str, int] = {}  # meter -> crossings
        self.first_ts = time.time()
        self.last_ts = self.first_ts

    def to_dict(self, fp_limit: int = 8) -> Dict[str, Any]:
        out: Dict[str, Any] = {"ns": self.ns, "db": self.db}
        for m in METERS:
            out[m] = round(self.meters.get(m, 0.0), 6)
        fps = list(self.by_fp.items())[-max(int(fp_limit), 0):]
        out["by_fp"] = [
            dict({"fingerprint": fp}, **{k: round(v, 6) for k, v in d.items()})
            for fp, d in reversed(fps)
        ]
        out["by_node"] = {
            n: {k: round(v, 6) for k, v in d.items()}
            for n, d in sorted(self.by_node.items())
        }
        out["bg_kinds"] = {k: round(v, 6) for k, v in sorted(self.bg_kinds.items())}
        out["breaches"] = dict(self.breaches)
        out["first_ts"] = round(self.first_ts, 3)
        out["last_ts"] = round(self.last_ts, 3)
        return out


_lock = _locks.Lock("accounting.store")
_store: "OrderedDict[Tuple[str, str], _Entry]" = OrderedDict()
_global: Dict[str, float] = {}  # conservation rollup — never evicted
_evicted = 0
# single-entry parse cache for budget specs, keyed by the spec STRING so
# a test monkeypatching cnf.TENANT_BUDGET_* takes effect immediately
_budget_cache: Dict[str, Dict[str, float]] = {}


def _key(ns: Optional[str], db: Optional[str]) -> Tuple[str, str]:
    # unscoped work (root statements with no USE, server internals) folds
    # into the ("", "") bucket so conservation still holds
    return (str(ns) if ns else "", str(db) if db else "")


# -------------------------------------------------------------- tenant context
# Which tenant the CURRENT unit of work executes for. Two carriers:
# - a contextvar, copied into scatter-pool threads by the existing
#   contextvars.copy_context().run plumbing;
# - a thread-keyed table (GIL-atomic dict ops, the stats.py pattern) the
#   profiler reads CROSS-thread — contextvars are invisible from outside.
_tenant_ctx: "contextvars.ContextVar[Optional[Tuple[str, str]]]" = (
    contextvars.ContextVar("accounting_tenant", default=None)
)
_active_by_thread: Dict[int, Tuple[str, str]] = {}


def activate(ns: Optional[str], db: Optional[str]):
    """Mark (ns, db) as the tenant executing on the current thread AND in
    the current context. Returns a token for deactivate(); nested
    activations restore the outer tenant."""
    key = _key(ns, db)
    ident = threading.get_ident()
    prev = _active_by_thread.get(ident)
    _active_by_thread[ident] = key
    ctx_tok = _tenant_ctx.set(key)
    return (ctx_tok, ident, prev)


def deactivate(token) -> None:
    ctx_tok, ident, prev = token
    try:
        _tenant_ctx.reset(ctx_tok)
    except ValueError:
        pass  # reset from a copied context — the copy dies with its thread
    if prev is None:
        _active_by_thread.pop(ident, None)
    else:
        _active_by_thread[ident] = prev


def current_tenant() -> Optional[Tuple[str, str]]:
    """The (ns, db) the current CONTEXT executes for — survives the
    contextvars copy into scatter/federation pool threads, which is how
    dispatch riders and bg registrations learn their tenant."""
    key = _tenant_ctx.get()
    if key is None:
        key = _active_by_thread.get(threading.get_ident())
    return key


def active_tenant(ident: Optional[int] = None) -> Optional[Tuple[str, str]]:
    """The (ns, db) executing on thread `ident` (default: current) — the
    profiler's cross-thread attribution read."""
    if ident is None:
        return current_tenant()
    return _active_by_thread.get(ident)


# ---------------------------------------------------------- per-statement tally
# Statement-local scratch accumulators, thread-keyed: deep call sites
# (the iterator's scan loops) tally rows without knowing the tenant or
# paying a store lock per chunk; the executor flushes the tally into its
# single end-of-statement charge(). Tally mutation is NOT meter mutation
# — the store is only ever written through charge().
_tally_by_thread: Dict[int, Dict[str, float]] = {}


def tally_begin() -> Optional[Dict[str, float]]:
    """Open a fresh statement tally on this thread; returns the previous
    tally (restore it via tally_end for nested statements)."""
    ident = threading.get_ident()
    prev = _tally_by_thread.get(ident)
    _tally_by_thread[ident] = {}
    return prev


def tally(**meters: float) -> None:
    """Accumulate into the current thread's open statement tally (no-op
    without one — scans outside a measured statement cost nobody)."""
    t = _tally_by_thread.get(threading.get_ident())
    if t is None:
        return
    for m, v in meters.items():
        if v:
            t[m] = t.get(m, 0.0) + float(v)


def tally_end(prev: Optional[Dict[str, float]]) -> Dict[str, float]:
    """Close this thread's tally, restoring `prev` (the tally_begin
    return); returns the accumulated meters for the flush charge."""
    ident = threading.get_ident()
    out = _tally_by_thread.pop(ident, None) or {}
    if prev is not None:
        _tally_by_thread[ident] = prev
    return out


# ------------------------------------------------------------------ the door
def charge(
    ns: Optional[str],
    db: Optional[str],
    *,
    fingerprint: Optional[str] = None,
    node: Optional[str] = None,
    bg_kind: Optional[str] = None,
    **meters: float,
) -> None:
    """THE write door: add `meters` to tenant (ns, db) — plus the
    fingerprint drill-down, the per-node breakdown (`node`, scatter
    charges) and the bg-kind breakdown (`bg_kind`) when given. Detects
    soft-budget crossings-from-below under the lock, emits the breach
    event + counter AFTER release (events/telemetry sit at lower lock
    levels and must never nest inside `accounting.store`)."""
    from surrealdb_tpu import cnf

    if not getattr(cnf, "TENANT_ACCOUNTING", True):
        return
    key = _key(ns, db)
    global _evicted
    breaches: List[Tuple[str, float, float]] = []
    evictions = 0
    with _lock:
        e = _store.get(key)
        if e is None:
            e = _store[key] = _Entry(*key)
        else:
            _store.move_to_end(key)
        for m, v in meters.items():
            if not v:
                continue
            v = float(v)
            was = e.meters.get(m, 0.0)
            e.meters[m] = was + v
            _global[m] = _global.get(m, 0.0) + v
            knob = _BUDGET_KNOBS.get(m)
            if knob is not None:
                limit = _budget_limit(knob, key[0])
                if limit is not None and was < limit <= was + v:
                    e.breaches[m] = e.breaches.get(m, 0) + 1
                    breaches.append((m, limit, was + v))
        if fingerprint:
            fpd = e.by_fp.get(fingerprint)
            if fpd is None:
                fpd = e.by_fp[fingerprint] = {}
            else:
                e.by_fp.move_to_end(fingerprint)
            for m, v in meters.items():
                if v:
                    fpd[m] = fpd.get(m, 0.0) + float(v)
            fp_cap = max(int(getattr(cnf, "TENANT_FP_CAP", 32)), 1)
            while len(e.by_fp) > fp_cap:
                e.by_fp.popitem(last=False)
        if node:
            nd = e.by_node.get(node)
            if nd is None:
                nd = e.by_node[node] = {}
            for m, v in meters.items():
                if v:
                    nd[m] = nd.get(m, 0.0) + float(v)
        if bg_kind:
            e.bg_kinds[bg_kind] = e.bg_kinds.get(bg_kind, 0.0) + float(
                meters.get("bg_s", 0.0) or 0.0
            )
        e.last_ts = time.time()
        cap = max(int(getattr(cnf, "TENANT_STORE_SIZE", 256)), 8)
        while len(_store) > cap:
            _store.popitem(last=False)
            _evicted += 1
            evictions += 1
    # side effects OUTSIDE the store lock
    from surrealdb_tpu import telemetry

    if evictions:
        telemetry.inc("tenant_evictions", by=float(evictions))
    for meter, limit, value in breaches:
        from surrealdb_tpu import events, tracing

        telemetry.inc("tenant_budget_breaches", ns=key[0])
        # the crossing statement's trace must stay resolvable: breach ->
        # /trace/:id is the budget plane's one-hop contract
        tracing.force_keep()
        events.emit(
            "tenant.budget_exceeded",
            ns=key[0], db=key[1], meter=meter,
            limit=round(limit, 6), value=round(value, 6),
            **({"fingerprint": fingerprint} if fingerprint else {}),
        )


def _budget_limit(knob: str, ns: str) -> Optional[float]:
    """Parse (cached) one budget knob's spec and resolve `ns`'s limit.
    Spec: plain float (every tenant) or ``ns:limit[,ns:limit,...]``."""
    from surrealdb_tpu import cnf

    spec = str(getattr(cnf, knob, "") or "").strip()
    if not spec:
        return None
    cache_key = f"{knob}={spec}"
    parsed = _budget_cache.get(cache_key)
    if parsed is None:
        parsed = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, sep, val = part.rpartition(":")
            try:
                parsed[name.strip() if sep else ""] = float(val)
            except ValueError:
                continue  # a malformed clause disables itself, not the rest
        _budget_cache.clear()  # one live spec per knob — drop stale parses
        _budget_cache[cache_key] = parsed
    limit = parsed.get(ns)
    return limit if limit is not None else parsed.get("")


# ------------------------------------------------------------------ views
def top(
    limit: int = 50, sort: str = "exec_s", fp_limit: int = 8
) -> List[dict]:
    """Tenants ordered by one meter, descending — the ``GET /tenants``
    payload. Unknown sort keys fall back to exec_s (bounded surface)."""
    key = sort if sort in _SORT_KEYS else "exec_s"
    with _lock:
        entries = [e.to_dict(fp_limit=fp_limit) for e in _store.values()]
    entries.sort(key=lambda e: (-(e.get(key) or 0), e["ns"], e["db"]))
    return entries[: max(int(limit), 1)]


def get(ns: Optional[str], db: Optional[str]) -> Optional[dict]:
    with _lock:
        e = _store.get(_key(ns, db))
        return e.to_dict() if e is not None else None


def size() -> int:
    with _lock:
        return len(_store)


def global_totals() -> Dict[str, float]:
    """The conservation rollup: every meter's all-tenant total, immune to
    eviction — per-tenant sums reconcile against this (and against the
    independent dispatch/telemetry counters the charge sites mirror)."""
    with _lock:
        return {m: round(v, 6) for m, v in sorted(_global.items())}


def snapshot(limit: int = 20) -> dict:
    """The bundle's `tenants` section: store state + top tenants."""
    with _lock:
        n, ev = len(_store), _evicted
    return {
        "tenants": n,
        "evicted": ev,
        "global": global_totals(),
        "top": top(limit=limit),
    }


def export_state(limit: int = 100) -> List[dict]:
    """Per-node entries for cluster federation (the `tenants` RPC op):
    node-UNtagged — the coordinator tags each with its member id."""
    return top(limit=limit)


def reset() -> None:
    """Drop every meter (tests, measurement windows)."""
    global _evicted
    with _lock:
        _store.clear()
        _global.clear()
        _evicted = 0
    _budget_cache.clear()
