"""Compile-event log: every XLA compile recorded, attributed, exportable.

Every distinct padded shape a jitted kernel is called with is a separate
XLA compile — seconds each, and the classic cause of an unexplained
latency swing when one is minted ON DEMAND inside a live query
instead of by the background shape warmers. This module wraps the kernel
call sites (idx/knn.py, idx/ivf.py, idx/graph_csr.py):

- the FIRST call per (subsystem, shape key) is the compile: its duration,
  subsystem, shape and mode land in a bounded event log, a
  `compile_events{subsystem,mode}` counter and an `xla_compile` duration
  histogram;
- `mode` is `prewarm` when a background warmer minted it, `on_demand` when
  it happened under (or on behalf of) a live request — in which case an
  `xla_compile` span is recorded into exactly ONE trace (the active
  request's, or the dispatch batch's first rider via the attribution
  contextvar dbs/dispatch.py sets) — the smoking gun for latency swings;
- subsequent calls count as `compile_cache{subsystem,shape,outcome=hit}`
  — riders of a coalesced batch see a cache hit, not a second compile.

Shape keys are value tuples of static dims (tile, dim, cap, k, ...), the
same things XLA keys its own cache on, so "first call per key" == "this
call traced + compiled". The log is bounded by SURREAL_COMPILE_LOG_CAP.

The registry below (KERNEL_SITES) makes the tracked sites ENUMERABLE:
every subsystem name ever passed to tracked() maps to the import path of
a `graftcheck_sites()` provider in the module that owns the kernel. The
provider declares the kernel's audit contract — representative shape
matrix, abstract-lowering builder, allowed collectives, declared output
dtypes — and `python -m scripts.graftcheck` lowers each one to
jaxpr/StableHLO and checks the GC001–GC004 contracts against the IR. A
new jitted kernel MUST register here (tests/test_graftcheck.py asserts
source-tracked subsystems ⊆ KERNEL_SITES), so it cannot ship unaudited.
"""

from __future__ import annotations

import contextvars
from surrealdb_tpu.utils import locks as _locks
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Deque, Optional, Tuple

# ---------------------------------------------------------------- registry
# subsystem -> "import.path:provider" of the module that owns the kernel.
# The provider is a zero-arg callable returning a list of audit-contract
# dicts (one per subsystem it hosts); scripts/graftcheck/registry.py
# resolves and validates them. Keys are EXACTLY the subsystem strings
# passed to tracked() — the registry-completeness test diffs the two.
KERNEL_SITES = {
    "knn_exact": "surrealdb_tpu.idx.knn:graftcheck_sites",
    "knn_sharded": "surrealdb_tpu.parallel.mesh:graftcheck_sites",
    "ivf": "surrealdb_tpu.idx.ivf:graftcheck_sites",
    "ivf_sharded": "surrealdb_tpu.parallel.mesh:graftcheck_sites",
    "knn_subset": "surrealdb_tpu.idx.knn:graftcheck_sites",
    "knn_subset_sharded": "surrealdb_tpu.parallel.mesh:graftcheck_sites",
    "graph_dense": "surrealdb_tpu.idx.graph_csr:graftcheck_sites",
    "graph_csc": "surrealdb_tpu.idx.graph_csr:graftcheck_sites",
    "graph_reach": "surrealdb_tpu.idx.graph_csr:graftcheck_sites",
    "graph_chain": "surrealdb_tpu.idx.graph_csr:graftcheck_sites",
    "bm25": "surrealdb_tpu.ops.bm25:graftcheck_sites",
    "column_agg": "surrealdb_tpu.ops.column_agg:graftcheck_sites",
    "ml_forward": "surrealdb_tpu.ml.model:graftcheck_sites",
}


_lock = _locks.Lock("compile_log")
_seen: set = set()  # (subsystem, shape_key) already compiled
_inflight: set = set()  # keys whose FIRST call is still inside tracked()
_events: Deque[dict] = deque(maxlen=512)  # re-bounded lazily from cnf

# dispatch attribution: the leader launches kernels with tracing detached
# (spans are re-parented per rider), so an on-demand compile under a batch
# would otherwise be unattributable. dbs/dispatch.py parks the FIRST
# rider's SpanCtx here for the duration of the launch/collect/retry call.
_attr_ctx: "contextvars.ContextVar[Optional[Any]]" = contextvars.ContextVar(
    "surreal_compile_attr", default=None
)


@contextmanager
def attribution(trace_ctx) -> Any:
    """Attribute any compile inside this block to `trace_ctx` (a tracing
    SpanCtx) when no trace is otherwise active."""
    token = _attr_ctx.set(trace_ctx)
    try:
        yield
    finally:
        _attr_ctx.reset(token)


def _cap() -> int:
    from surrealdb_tpu import cnf

    return max(cnf.COMPILE_LOG_CAP, 16)


def seen(subsystem: str, shape: Tuple) -> bool:
    with _lock:
        return (subsystem, shape) in _seen


@contextmanager
def tracked(subsystem: str, shape: Tuple, prewarmed: bool = False):
    """Wrap one shape-keyed kernel invocation. First call per key = the
    compile event (timed, logged, attributed); later calls = cache hits."""
    global _events
    from surrealdb_tpu import telemetry

    key = (subsystem, tuple(shape))
    with _lock:
        first = key not in _seen
        if first:
            _seen.add(key)
            _inflight.add(key)
            waiting = False
        else:
            waiting = key in _inflight
    shape_label = "x".join(str(s) for s in shape)
    if not first:
        if not waiting:
            telemetry.inc(
                "compile_cache", subsystem=subsystem, shape=shape_label, outcome="hit"
            )
            yield False
            return
        # the first call is STILL compiling on another thread (e.g. a
        # prewarm warmer won the race): this caller blocks behind XLA's
        # compile lock for the full duration — record that wait as its own
        # attributed event, not a phantom instant "hit"
        telemetry.inc(
            "compile_cache", subsystem=subsystem, shape=shape_label, outcome="wait"
        )
        t0w = time.perf_counter()
        werr: Optional[BaseException] = None
        try:
            yield False
        except BaseException as e:
            werr = e
            raise
        finally:
            from surrealdb_tpu import tracing

            dur = time.perf_counter() - t0w
            telemetry.observe("xla_compile_wait", dur, subsystem=subsystem)
            sc = tracing.current()
            wctx = sc if sc is not None else _attr_ctx.get()
            if wctx is not None:
                tracing.record_span_into(
                    wctx, "xla_compile_wait",
                    {"subsystem": subsystem, "shape": shape_label},
                    t0w, dur, werr,
                )
        return
    telemetry.inc(
        "compile_cache", subsystem=subsystem, shape=shape_label, outcome="miss"
    )
    t0 = time.perf_counter()
    err: Optional[BaseException] = None
    try:
        yield True
    except BaseException as e:
        err = e
        raise
    finally:
        dur = time.perf_counter() - t0
        from surrealdb_tpu import tracing

        with _lock:
            _inflight.discard(key)
            if err is not None:
                # a failed first call did NOT leave a cached executable:
                # the next call through this shape is the real compile and
                # must be recorded as one, not mislogged as a cache hit
                _seen.discard(key)
        ctx = None
        if not prewarmed:
            sc = tracing.current()
            ctx = sc if sc is not None else _attr_ctx.get()
        mode = "prewarm" if prewarmed else ("on_demand" if ctx is not None else "startup")
        trace_id = ctx.trace.trace_id if ctx is not None else None
        event = {
            "ts": time.time(),
            "subsystem": subsystem,
            "shape": shape_label,
            "duration_ms": round(dur * 1e3, 3),
            "mode": mode,
            "trace_id": trace_id,
            "error": type(err).__name__ if err is not None else None,
        }
        with _lock:
            if _events.maxlen != _cap():
                _events = deque(_events, maxlen=_cap())
            _events.append(event)
        telemetry.inc("compile_events", subsystem=subsystem, mode=mode)
        telemetry.observe("xla_compile", dur, subsystem=subsystem, mode=mode)
        if ctx is not None:
            # exactly one trace carries the compile span: the request that
            # triggered it (or led the batch that did)
            tracing.record_span_into(
                ctx,
                "xla_compile",
                {"subsystem": subsystem, "shape": shape_label, "mode": mode},
                t0,
                dur,
                err,
            )
            # pin that trace into the store regardless of tail sampling —
            # the event's trace_id must resolve via /trace/:id, and an
            # on-demand compile IS the smoking gun the store exists for
            ctx.trace.force = True


# ------------------------------------------------------------------ views
def events(since: Optional[float] = None) -> list:
    """Logged compile events, oldest first (optionally only ts >= since)."""
    with _lock:
        out = list(_events)
    if since is not None:
        out = [e for e in out if e["ts"] >= since]
    return out


def snapshot() -> dict:
    """Compile-log section of the debug bundle."""
    from surrealdb_tpu import telemetry

    evs = events()
    hits: dict = {}
    for labels, v in telemetry.counters_matching("compile_cache").items():
        d = dict(labels)
        hits[f"{d.get('subsystem')}:{d.get('shape')}:{d.get('outcome')}"] = int(v)
    return {
        "events": evs,
        "shapes_compiled": len(evs),
        "on_demand": sum(1 for e in evs if e["mode"] == "on_demand"),
        "prewarmed": sum(1 for e in evs if e["mode"] == "prewarm"),
        "cache": hits,
    }


def reset() -> None:
    with _lock:
        _seen.clear()
        _inflight.clear()
        _events.clear()
