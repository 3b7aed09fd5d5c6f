"""Telemetry: labeled metrics, histograms, spans, slow-query log, profiler.

Role of the reference's telemetry stack (reference: src/telemetry/mod.rs:
43-99 — OTEL traces + HTTP/WS request metrics, RPC spans). This
environment has no OTLP collector, so the equivalent surface is:

- a process-global metrics registry: labeled counters, labeled gauges,
  and labeled histograms with fixed log-scale buckets, rendered as valid
  Prometheus text exposition (`_bucket`/`_sum`/`_count`) at GET /metrics;
- duration histograms fed by `span()`/`observe()` around statement
  execution, device dispatches, RPC methods and HTTP requests;
- the interpreter's collections (one `gc.callbacks` hook): the
  `gc_collections{gen}` counter, the `gc_pause{gen}` duration histogram,
  and a `gc_pause` span in the trace active on the collecting thread; in a
  server's process the hook also freezes the survivors of a long full
  collection (freeze_long_lived; the `gc_frozen_objects` gauge);
- a structured slow-query ring buffer (sql, duration, plan summary,
  dispatch stats, error) drained via `snapshot()` or GET /slow;
- span recording around statement execution and device dispatches,
  enabled by `--profile` / SURREAL_PROFILE=1 (spans cost nothing when
  disabled), drained via `snapshot()` or INFO-style inspection;
- `jax.profiler` hooks: `start_trace()/stop_trace()` capture a device
  trace directory, and `trace_annotation()`
  labels dispatch launch/collect phases inside it (and under `--profile`;
  in nobody else's trace). A trace that was asked for and cannot start
  raises.
"""

from __future__ import annotations

import gc
import threading
from surrealdb_tpu import tracing
from surrealdb_tpu.utils import locks as _locks
import time
from bisect import bisect_left
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Deque, Dict, List, Optional, Tuple

_lock = _locks.Lock("telemetry.registry")
_enabled = False
_spans: Deque[Tuple[str, float, float]] = deque(maxlen=4096)  # (name, start, dur_s)

_LabelKey = Tuple[Tuple[str, str], ...]
_counters: Dict[Tuple[str, _LabelKey], float] = {}
_gauges: Dict[Tuple[str, _LabelKey], float] = {}
# family -> (buckets, {labels: [counts per bucket + overflow, sum, count, max]})
_hists: Dict[str, Tuple[Tuple[float, ...], Dict[_LabelKey, list]]] = {}
_DURATION_SUFFIX = "_duration_seconds"

# fixed log-scale buckets — one shared shape per unit so every duration /
# size / count metric is comparable and the exposition stays small
DURATION_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)
SIZE_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
COUNT_BUCKETS: Tuple[float, ...] = (
    1, 4, 16, 64, 256, 1024, 4096, 16384, 65536, 262144, 1048576,
)

_SLOW_LOG_SIZE = 128
_slow: Deque[dict] = deque(maxlen=_SLOW_LOG_SIZE)

_tls = threading.local()  # per-thread plan notes for the slow-query log


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = on


def enabled() -> bool:
    return _enabled


def _key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


# ------------------------------------------------------------------ counters
def inc(name: str, by: float = 1.0, **labels) -> None:
    key = (name, _key(labels))
    with _lock:
        _counters[key] = _counters.get(key, 0.0) + by


def inc_each(name: str, label: str, by: Dict[str, float]) -> None:
    """One counter family's series moved together, under one acquisition of
    the registry lock: `by` maps a value of `label` to its increment."""
    with _lock:
        for value, d in by.items():
            key = (name, ((label, str(value)),))
            _counters[key] = _counters.get(key, 0.0) + d


def get_counter(name: str, **labels) -> float:
    with _lock:
        return _counters.get((name, _key(labels)), 0.0)


def counters_matching(name: str) -> Dict[_LabelKey, float]:
    """All label-series of one counter family: {labels_tuple: value}."""
    with _lock:
        return {labels: v for (n, labels), v in _counters.items() if n == name}


def error_class(e: BaseException) -> str:
    """Stable low-cardinality error label for counters."""
    return type(e).__name__


# ------------------------------------------------------------------ gauges
def gauge_add(name: str, delta: float, **labels) -> None:
    key = (name, _key(labels))
    with _lock:
        _gauges[key] = _gauges.get(key, 0.0) + delta


def gauge_set(name: str, value: float, **labels) -> None:
    with _lock:
        _gauges[(name, _key(labels))] = float(value)


def gauges_matching(name: str) -> Dict[_LabelKey, float]:
    """All label-series of one gauge family: {labels_tuple: value}."""
    with _lock:
        return {labels: v for (n, labels), v in _gauges.items() if n == name}


# ------------------------------------------------------------------ histograms
def _hist_observe(family: str, buckets: Tuple[float, ...], value: float, labels: Dict) -> None:
    lk = _key(labels)
    with _lock:
        fam = _hists.get(family)
        if fam is None:
            fam = _hists[family] = (buckets, {})
        # first registration wins: a call site passing different buckets for
        # the same family is folded into the registered shape (bisect below
        # uses fam[0]) — a bookkeeping mismatch must never abort the query
        # path this instruments
        _, series = fam
        h = series.get(lk)
        if h is None:
            # per-bucket counts + overflow slot, then sum, count, max
            h = series[lk] = [0] * (len(fam[0]) + 1) + [0.0, 0, value]
        h[bisect_left(fam[0], value)] += 1
        h[-3] += value
        h[-2] += 1
        h[-1] = max(h[-1], value)


def observe_hist(name: str, value: float, buckets: Tuple[float, ...] = SIZE_BUCKETS, **labels) -> None:
    """Generic labeled histogram (batch widths, candidate counts, ...)."""
    _hist_observe(name, buckets, float(value), labels)


def observe(name: str, seconds: float, **labels) -> None:
    """Duration histogram `surreal_<name>_duration_seconds`; its count, sum
    and max are also the `durations` summary of snapshot()."""
    _hist_observe(name + _DURATION_SUFFIX, DURATION_BUCKETS, seconds, labels)


def stage(name: str, start: float, seconds: float, **labels) -> None:
    """One timed stage of load-path work (mirror scan, cast, upload, IVF
    training, graph operator builds), measured by the caller: a span in the
    trace of the statement that pays for it or, where a background task
    does the work, an entry in that task's record."""
    ctx = tracing.current()
    if ctx is not None:
        tracing.record_span_into(ctx, name, labels, start, seconds)
    else:
        from surrealdb_tpu import bg

        bg.note_stage(name, seconds, labels)


# ------------------------------------------------------------------ thread CPU
# What the interpreter was given, by the role of the thread that took it
# (`exec`: the bg:net_exec workers; `loop`: the bg:net_loop threads): each
# thread leaves `time.thread_time()`, its own CPU clock, in its slot when a
# task or a pass ends (cpu_slot_note). A slot has ONE writer, its thread,
# and the store of a float into a list cell is atomic under the interpreter
# lock, so the writers take no lock and share no `+=`; `_lock` guards only
# the table (a thread's first and last act) and the readers' sum.
# Process-wide, as the interpreter is; monotone, and reset() leaves it alone.
_cpu_slots: Dict[str, Dict[int, list]] = {}  # role -> {id(slot): slot}
_cpu_ended: Dict[str, float] = {}  # role -> the last readings of threads that ended
# A thread reads its clock at most this often. On the host of the chip the
# call is a system call (5.6 us back to back, far more from a cold start:
# two a dispatch cost a one-session cell 0.2 ms a statement) where
# `perf_counter` is 0.12 us, and the clock it reads ticks every 10 ms
# (PERF.md section 6, PR 49): one read a task and a pass cost 3-5% of
# `snbsf1.hop3_c8`'s rate. Nine threads at four reads a second cost nothing,
# and a 30 s window's edge is off by a quarter second of a thread's CPU.
CPU_SLOT_EVERY_S = 0.25


def cpu_slot(role: str) -> list:
    """A slot of the calling thread under `role`: [its CPU clock as last
    read, the `perf_counter` of that read]. The thread calls cpu_slot_note()
    whenever a piece of work ends, and cpu_slot_end() when it ends itself."""
    slot = [0.0, float("-inf")]
    with _lock:
        _cpu_slots.setdefault(role, {})[id(slot)] = slot
    return slot


def cpu_slot_note(slot: list) -> None:
    """A piece of the calling thread's work has ended: leave its CPU clock in
    its slot, if the last reading is CPU_SLOT_EVERY_S old."""
    now = time.perf_counter()
    if now - slot[1] >= CPU_SLOT_EVERY_S:
        slot[0], slot[1] = time.thread_time(), now


def cpu_slot_end(role: str, slot: list) -> None:
    """The calling thread ends: its last reading, kept in the role's sum."""
    slot[0] = max(slot[0], time.thread_time())
    with _lock:
        if _cpu_slots.get(role, {}).pop(id(slot), None) is not None:
            _cpu_ended[role] = _cpu_ended.get(role, 0.0) + slot[0]


def cpu_seconds() -> Dict[str, float]:
    """{role: CPU seconds its threads have burned since the process
    started}, as they last wrote them: no clock is read here, so what a
    thread has burned since its last reading (at most CPU_SLOT_EVERY_S and
    one task or pass) is not in its role's sum yet."""
    with _lock:
        return {
            role: _cpu_ended.get(role, 0.0) + sum(s[0] for s in slots.values())
            for role, slots in _cpu_slots.items()
        }


# ------------------------------------------------------------------ collections
def _gc_zero() -> list:
    return [0] * (len(DURATION_BUCKETS) + 1) + [0.0, 0, 0.0]


# generation -> histogram cell of `gc_pause`, laid out as a `_hists` series.
# The hook runs inside whichever allocation tripped the collector, on a
# thread that may already hold `_lock`, so it takes no lock: the interpreter
# runs one collection at a time, and snapshot() / export_state() /
# render_prometheus() fold the cells into the registry under `_lock`.
_gc_cells: Dict[int, list] = {g: _gc_zero() for g in range(3)}
_gc_t0 = 0.0

# A full collection walks every tracked object with every thread stopped:
# 2.2-2.4 s at the 9.3 million objects of a loaded SF3 graph, 39 times
# during its load and again whenever a quarter as many objects have grown old
# since (PERF.md section 6, PR 44). Where the process is a server's
# (freeze_long_lived), the survivors of a full collection that stopped it
# this long are the data it holds: they are moved out of the collector's
# sight (gc.freeze), and the next full collection walks what came since.
# What is frozen is still freed when its last reference goes; a reference
# cycle that forms among frozen objects and is dropped is not.
GC_FREEZE_OVER_S = 0.1
_gc_freeze = False


def freeze_long_lived() -> None:
    """From now on, freeze what survives a full collection that took
    GC_FREEZE_OVER_S or longer (net/server.py::Server: a process that holds
    a datastore to serve it; a library user's process keeps its collector
    as it is). `gc_frozen_objects` on /metrics says how many that is."""
    global _gc_freeze
    _gc_freeze = True


def _gc_hook(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    t0 = _gc_t0
    dur = time.perf_counter() - t0
    gen = info["generation"]
    if gen == 2 and _gc_freeze and dur >= GC_FREEZE_OVER_S:
        gc.freeze()
    h = _gc_cells[gen]
    h[bisect_left(DURATION_BUCKETS, dur)] += 1
    h[-3] += dur
    h[-2] += 1
    h[-1] = max(h[-1], dur)
    ctx = tracing.current()
    if ctx is not None:
        tracing.record_span_into(ctx, "gc_pause", {"gen": gen}, t0, dur)


def _fold_gc_locked() -> None:
    for gen, h in _gc_cells.items():
        if h[-2]:
            lk = (("gen", str(gen)),)
            _counters[("gc_collections", lk)] = float(h[-2])
            fam = _hists.setdefault("gc_pause" + _DURATION_SUFFIX, (DURATION_BUCKETS, {}))
            fam[1][lk] = list(h)


gc.callbacks.append(_gc_hook)


@contextmanager
def span(name: str, **labels: str):
    """Timed span: always feeds the duration histograms; becomes a node in
    the active request's span tree (tracing.py) when one exists; records
    the flat profiling entry only while profiling is enabled (reference
    #[instrument] spans). With no active trace and profiling off the extra
    cost is one ContextVar read."""
    t0 = time.perf_counter()
    tok = tracing.push()
    err = None
    try:
        yield
    except BaseException as e:
        err = e
        raise
    finally:
        dur = time.perf_counter() - t0
        observe(name, dur, **labels)
        if tok is not None:
            tracing.pop(tok, name, labels, t0, dur, err)
        if _enabled:
            with _lock:
                _spans.append((name, t0, dur))


# ------------------------------------------------------------------ slow queries
def record_slow_query(entry: dict) -> None:
    """Append one structured slow-statement record to the ring buffer
    (replaces the print-based warning; reference: query duration warnings
    in telemetry/metrics)."""
    with _lock:
        _slow.append(entry)


def slow_queries() -> List[dict]:
    with _lock:
        return list(_slow)


# ------------------------------------------------------------------ error log
# Counters are label-bounded so they can't carry a trace_id; this bounded
# ring is the joinable side of statement_errors: each entry cites the
# request's trace_id + session info (ns/db/auth LEVEL — never tokens).
_ERROR_LOG_SIZE = 256
_errors: Deque[dict] = deque(maxlen=_ERROR_LOG_SIZE)


def record_error(entry: dict) -> None:
    with _lock:
        _errors.append(entry)


def recent_errors() -> List[dict]:
    with _lock:
        return list(_errors)


# ------------------------------------------------------------------ plan notes
def note_plan(note: dict) -> None:
    """Record a plan decision for the CURRENT thread's statement; the
    executor drains these into the slow-query record so a slow statement's
    entry says which index/strategy actually served it."""
    lst = getattr(_tls, "plan_notes", None)
    if lst is None:
        lst = _tls.plan_notes = []
    lst.append(note)
    del lst[:-8]  # bound per-statement accumulation


def drain_plan_notes() -> List[dict]:
    lst = getattr(_tls, "plan_notes", None)
    if not lst:
        return []
    out = list(lst)
    del lst[:]
    return out


# ------------------------------------------------------------------ profiler
_trace_dir: Optional[str] = None


def start_trace(outdir: str) -> None:
    """Begin a `jax.profiler` trace capture into `outdir`. A trace that was
    asked for and cannot start raises: the caller wanted device evidence,
    and a run without it must not look like one with it."""
    global _trace_dir
    if _trace_dir is not None:
        return
    import jax

    jax.profiler.start_trace(outdir)
    _trace_dir = outdir


def stop_trace() -> Optional[str]:
    """Finish the in-flight trace capture; returns its directory or None
    when no capture was running."""
    global _trace_dir
    if _trace_dir is None:
        return None
    out, _trace_dir = _trace_dir, None
    import jax

    jax.profiler.stop_trace()
    return out


def trace_annotation(name: str):
    """Label a dispatch phase inside the device trace. Live only under
    `surreal start --profile` (enable()) or between this module's
    start_trace() and stop_trace(); otherwise a `nullcontext`. A trace
    someone else started through `jax.profiler` (the benchmark's slice)
    therefore holds no such label: what divides a dispatch there are the
    spans and the stats() sums of dbs/dispatch.py."""
    if not _enabled and _trace_dir is None:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------------ snapshot / reset
def snapshot() -> dict:
    """Current metrics + slow queries + (when profiling) recent spans."""
    with _lock:
        _fold_gc_locked()
        return {
            "counters": {
                name + (_fmt_labels(labels) if labels else ""): v
                for (name, labels), v in _counters.items()
            },
            "gauges": {
                name + (_fmt_labels(labels) if labels else ""): v
                for (name, labels), v in _gauges.items()
            },
            "durations": {
                fam[: -len(_DURATION_SUFFIX)] + (_fmt_labels(labels) if labels else ""): {
                    "count": h[-2],
                    "total_s": round(h[-3], 6),
                    "max_s": round(h[-1], 6),
                }
                for fam, (_, series) in _hists.items()
                if fam.endswith(_DURATION_SUFFIX)
                for labels, h in series.items()
            },
            "histograms": {
                fam + (_fmt_labels(labels) if labels else ""): {
                    "count": h[-2],
                    "sum": round(h[-3], 6),
                    "max": round(h[-1], 6),
                }
                for fam, (_, series) in _hists.items()
                for labels, h in series.items()
            },
            "slow_queries": list(_slow),
            "errors": list(_errors),
            "spans": [
                {"name": n, "start": s, "dur_ms": round(dur * 1e3, 3)}
                for n, s, dur in list(_spans)
            ]
            if _enabled
            else [],
        }


def reset() -> None:
    with _lock:
        _counters.clear()
        _gauges.clear()
        _hists.clear()
        for h in _gc_cells.values():
            h[:] = _gc_zero()
        _spans.clear()
        _slow.clear()
        _errors.clear()


# ------------------------------------------------------------------ node metrics
def collect_node_metrics(ds=None) -> None:
    """Refresh process/node-level gauges (reference: the runtime metrics
    the OTEL stack exports per node). Called by the /metrics handler right
    before rendering, so scrapes see current values: process RSS, live
    WS sessions (ws_connections gauge, maintained elsewhere), live-query
    subscriptions, and per-device memory when the backend reports it (CPU
    returns None). XLA compile hits/misses are the `compile_cache` counter
    compile_log keeps."""
    import sys

    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        import os as _os

        gauge_set(
            "process_resident_memory_bytes", rss_pages * _os.sysconf("SC_PAGE_SIZE")
        )
    except (OSError, ValueError, IndexError):
        pass
    gauge_set("gc_frozen_objects", gc.get_freeze_count())
    if ds is not None and getattr(ds, "notifications", None) is not None:
        gauge_set("live_queries", ds.notifications.live_count())
    # workload statistics plane: how many statement shapes the bounded
    # LRU currently tracks (evictions are the counter next to it)
    try:
        from surrealdb_tpu import stats

        gauge_set("statement_fingerprints", stats.size())
    except Exception:  # noqa: BLE001 — metrics must never fail a scrape
        inc("scrape_section_errors", section="stats")
    # flight recorder: live background-task gauges + per-subsystem memory
    # watermarks for the engine's device-bound mirrors
    try:
        from surrealdb_tpu import bg

        bg.export_gauges()
    except Exception:  # noqa: BLE001 — metrics must never fail a scrape
        inc("scrape_section_errors", section="bg_gauges")
    # network plane: admission queue depths + write-queue backpressure, so
    # a scrape shows where bytes and requests are piling up RIGHT NOW
    try:
        from surrealdb_tpu.net import loop as _netloop
        from surrealdb_tpu.net import qos as _netqos

        nd = _netloop.queue_depths()
        gauge_set("net_open_connections", nd["conns"])
        gauge_set("net_write_queued_bytes", nd["write_queued_bytes"])
        qd = _netqos.queue_depths()
        # aggregate series only (label cardinality stays bounded); the
        # per-tenant breakdown lives in the bundle's `net` section
        gauge_set("net_admission_queued", qd["queued"])
        gauge_set("net_admission_inflight", qd["inflight"])
    except Exception:  # noqa: BLE001 — metrics must never fail a scrape
        inc("scrape_section_errors", section="net")
    if ds is not None:
        try:
            for subsystem, nbytes in mirror_memory_bytes(ds).items():
                gauge_set("mirror_memory_bytes", nbytes, subsystem=subsystem)
        except Exception:  # noqa: BLE001 — metrics must never fail a scrape
            inc("scrape_section_errors", section="mirror_memory")
    if "jax" in sys.modules:
        try:
            import jax

            for d in jax.local_devices():
                ms = d.memory_stats()
                if ms and "bytes_in_use" in ms:
                    gauge_set(
                        "device_memory_bytes_in_use",
                        ms["bytes_in_use"],
                        device=str(d.id),
                    )
        except Exception:  # noqa: BLE001 — metrics must never fail a scrape
            inc("scrape_section_errors", section="device_memory")


def mirror_memory_bytes(ds) -> Dict[str, int]:
    """Host-array bytes held per mirror subsystem (vector matrices, IVF
    list tables, graph CSR arrays, column mirrors) — the per-subsystem
    memory watermark the flight recorder attributes device pressure to.
    Host nbytes == device upload size for every mirror (device arrays are
    produced by jnp.asarray over these), so this is backend-independent."""
    out = {"vector_mirror": 0, "ivf": 0, "graph_csr": 0, "column_mirror": 0}
    stores = getattr(ds, "index_stores", None)
    if stores is not None:
        with stores._lock:  # noqa: SLF001 — read-only snapshot
            mirrors = list(stores._stores.values())  # noqa: SLF001
        for m in mirrors:
            data = getattr(m, "data", None)
            if data is not None and hasattr(data, "nbytes"):
                out["vector_mirror"] += int(data.nbytes)
            ivf = getattr(m, "ivf", None)
            if ivf is not None:
                cents = getattr(ivf, "centroids", None)
                if cents is not None and hasattr(cents, "nbytes"):
                    out["ivf"] += int(cents.nbytes)
                out["ivf"] += 8 * int(getattr(ivf, "_n", 0) or 0)
    gm = getattr(ds, "graph_mirrors", None)
    if gm is not None:
        with gm._lock:  # noqa: SLF001
            csrs = list(gm._m.values())  # noqa: SLF001
        for c in csrs:
            for arr in (c.indptr, c.indices):
                if arr is not None:
                    out["graph_csr"] += int(arr.nbytes)
    cm = getattr(ds, "column_mirrors", None)
    if cm is not None:
        with cm._lock:  # noqa: SLF001
            cols = list(cm._mirrors.values())  # noqa: SLF001
        for mirror in cols:
            for col in mirror.columns.values():
                out["column_mirror"] += int(col.tags.nbytes) + int(col.nums.nbytes)
    return out


# ------------------------------------------------------------------ exposition
def _esc(v: str) -> str:
    """Prometheus label-value escaping: backslash, double-quote, newline."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: _LabelKey, extra: Optional[Tuple[str, str]] = None) -> str:
    parts = [f'{k}="{_esc(v)}"' for k, v in labels]
    if extra is not None:
        parts.append(f'{extra[0]}="{_esc(extra[1])}"')
    return "{" + ",".join(parts) + "}" if parts else ""


def _num(v: float) -> str:
    return repr(v) if isinstance(v, float) and not v.is_integer() else str(int(v))


def _bucket_label(b: float) -> str:
    return repr(b) if isinstance(b, float) and not float(b).is_integer() else str(int(b))


def export_state() -> dict:
    """Raw registry state for cluster federation (cluster/rpc.py `metrics`
    op): JSON-able — label tuples become dicts, histogram series become
    [family, buckets, labels, cells]. The coordinator re-labels every
    series with node=<id> and renders one merged exposition."""
    with _lock:
        _fold_gc_locked()
        return {
            "counters": [[n, dict(k), v] for (n, k), v in _counters.items()],
            "gauges": [[n, dict(k), v] for (n, k), v in _gauges.items()],
            "hists": [
                [fam, list(buckets), dict(lk), list(h)]
                for fam, (buckets, series) in _hists.items()
                for lk, h in series.items()
            ],
        }


def render_prometheus_federated(states: Dict[str, Optional[dict]]) -> str:
    """One Prometheus exposition for the WHOLE cluster (`/metrics?cluster=1`
    on the coordinator): every member's series re-labeled `node=<id>`
    (Monarch-style region labeling — one scrape, per-node attribution).
    Degraded-tolerant: a member whose scrape failed (state None)
    contributes only `surreal_cluster_scrape_up{node="<id>"} 0`, and the
    scrape still succeeds."""
    counters: Dict[str, List[Tuple[_LabelKey, float]]] = {}
    gauges: Dict[str, List[Tuple[_LabelKey, float]]] = {}
    hists: Dict[str, Tuple[Tuple[float, ...], List[Tuple[_LabelKey, list]]]] = {}
    for node in sorted(states):
        st = states[node]
        gauges.setdefault("cluster_scrape_up", []).append(
            (_key({"node": node}), 0.0 if st is None else 1.0)
        )
        if st is None:
            continue
        for n, labels, v in st.get("counters") or []:
            counters.setdefault(str(n), []).append(
                (_key(dict(labels, node=node)), float(v))
            )
        for n, labels, v in st.get("gauges") or []:
            gauges.setdefault(str(n), []).append(
                (_key(dict(labels, node=node)), float(v))
            )
        for fam, buckets, labels, cells in st.get("hists") or []:
            entry = hists.setdefault(str(fam), (tuple(buckets), []))
            if len(entry[0]) == len(buckets):  # shape-mismatched series drop
                entry[1].append((_key(dict(labels, node=node)), list(cells)))

    lines: List[str] = []
    for name in sorted(counters):
        fam = f"surreal_{name}_total"
        lines.append(f"# TYPE {fam} counter")
        for labels, v in sorted(counters[name]):
            lines.append(f"{fam}{_fmt_labels(labels)} {_num(v)}")
    for name in sorted(gauges):
        fam = f"surreal_{name}"
        lines.append(f"# TYPE {fam} gauge")
        for labels, v in sorted(gauges[name]):
            lines.append(f"{fam}{_fmt_labels(labels)} {_num(v)}")
    for family in sorted(hists):
        buckets, series = hists[family]
        fam = f"surreal_{family}"
        lines.append(f"# TYPE {fam} histogram")
        for labels, h in sorted(series):
            cum = 0
            for i, b in enumerate(buckets):
                cum += h[i]
                lines.append(
                    f"{fam}_bucket{_fmt_labels(labels, ('le', _bucket_label(b)))} {cum}"
                )
            cum += h[len(buckets)]
            lines.append(f"{fam}_bucket{_fmt_labels(labels, ('le', '+Inf'))} {cum}")
            lines.append(f"{fam}_sum{_fmt_labels(labels)} {h[-3]:.6f}")
            lines.append(f"{fam}_count{_fmt_labels(labels)} {h[-2]}")
    return "\n".join(lines) + "\n"


def render_prometheus() -> str:
    """Valid Prometheus text exposition of counters, gauges and histograms
    (reference telemetry/metrics/http/, ws/). Label values are escaped;
    histograms render cumulative `_bucket{le=...}` + `_sum` + `_count`."""
    lines: List[str] = []
    with _lock:
        _fold_gc_locked()
        by_counter: Dict[str, List[Tuple[_LabelKey, float]]] = {}
        for (name, labels), v in _counters.items():
            by_counter.setdefault(name, []).append((labels, v))
        for name in sorted(by_counter):
            fam = f"surreal_{name}_total"
            lines.append(f"# TYPE {fam} counter")
            for labels, v in sorted(by_counter[name]):
                lines.append(f"{fam}{_fmt_labels(labels)} {_num(v)}")

        by_gauge: Dict[str, List[Tuple[_LabelKey, float]]] = {}
        for (name, labels), v in _gauges.items():
            by_gauge.setdefault(name, []).append((labels, v))
        for name in sorted(by_gauge):
            fam = f"surreal_{name}"
            lines.append(f"# TYPE {fam} gauge")
            for labels, v in sorted(by_gauge[name]):
                lines.append(f"{fam}{_fmt_labels(labels)} {_num(v)}")

        for family in sorted(_hists):
            buckets, series = _hists[family]
            fam = f"surreal_{family}"
            lines.append(f"# TYPE {fam} histogram")
            for labels in sorted(series):
                h = series[labels]
                cum = 0
                for i, b in enumerate(buckets):
                    cum += h[i]
                    lines.append(
                        f"{fam}_bucket{_fmt_labels(labels, ('le', _bucket_label(b)))} {cum}"
                    )
                cum += h[len(buckets)]
                lines.append(f"{fam}_bucket{_fmt_labels(labels, ('le', '+Inf'))} {cum}")
                lines.append(f"{fam}_sum{_fmt_labels(labels)} {h[-3]:.6f}")
                lines.append(f"{fam}_count{_fmt_labels(labels)} {h[-2]}")
    return "\n".join(lines) + "\n"
