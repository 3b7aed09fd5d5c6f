"""Environment-configurable statics.

Mirrors the role of the reference's `SURREAL_*` env-parsed config statics
(reference: core/src/cnf/mod.rs:17-97). Values are read once at import.

This module is the ONLY sanctioned environment reader (graftlint GL003):
every other module takes its knobs from a constant below or, for
late-bound / dynamically-named variables, through the public `env_*`
helpers — so `python -m scripts.graftlint` can prove no configuration
enters the engine anywhere else.
"""

from __future__ import annotations

import os


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


# ------------------------------------------------------------ public helpers
# Late-bound reads for callers whose variable NAMES are dynamic (capability
# flags) or whose values change within a process lifetime (pytest's
# PYTEST_CURRENT_TEST). Everything else should be a module constant.
def env_str(name: str, default=None):
    return os.environ.get(name, default)


def env_bool(name: str, default: bool = False) -> bool:
    return _env_bool(name, default)


def env_int(name: str, default: int = 0) -> int:
    return _env_int(name, default)


def env_float(name: str, default: float = 0.0) -> float:
    return _env_float(name, default)


def under_pytest() -> bool:
    """True while pytest is executing a test (set/cleared per test by
    pytest itself, so this must be a live read, not an import-time knob)."""
    return bool(os.environ.get("PYTEST_CURRENT_TEST"))


# Execution limits
MAX_COMPUTATION_DEPTH = _env_int("SURREAL_MAX_COMPUTATION_DEPTH", 120)
MAX_CONCURRENT_TASKS = _env_int("SURREAL_MAX_CONCURRENT_TASKS", 64)
IDIOM_RECURSION_LIMIT = _env_int("SURREAL_IDIOM_RECURSION_LIMIT", 256)
MAX_QUERY_PARSING_DEPTH = _env_int("SURREAL_MAX_QUERY_PARSING_DEPTH", 1100)
MAX_OBJECT_PARSING_DEPTH = _env_int("SURREAL_MAX_OBJECT_PARSING_DEPTH", 100)

# KV scan batching
NORMAL_FETCH_SIZE = _env_int("SURREAL_NORMAL_FETCH_SIZE", 500)
MAX_STREAM_BATCH_SIZE = _env_int("SURREAL_MAX_STREAM_BATCH_SIZE", 1000)
EXPORT_BATCH_SIZE = _env_int("SURREAL_EXPORT_BATCH_SIZE", 1000)
INDEXING_BATCH_SIZE = _env_int("SURREAL_INDEXING_BATCH_SIZE", 250)
# row count past which INSERT INTO t $rows takes the bulk write path
BULK_INSERT_MIN = _env_int("SURREAL_BULK_INSERT_MIN", 64)
# embedded scripting limits (reference SCRIPTING_MAX_* cnf/mod.rs:56-61 —
# memory/stack caps; here an op budget + call-depth cap play that role)
SCRIPTING_MAX_OPS = _env_int("SURREAL_SCRIPTING_MAX_OPS", 2_000_000)
SCRIPTING_MAX_STACK_DEPTH = _env_int("SURREAL_SCRIPTING_MAX_STACK_DEPTH", 128)
# file backend: fsync the WAL on every commit (power-loss durability)
SYNC_DATA = _env_int("SURREAL_SYNC_DATA", 0) != 0
# file backend: WAL size that triggers snapshot compaction
WAL_COMPACT_MIN = _env_int("SURREAL_WAL_COMPACT_MIN", 8 * 1024 * 1024)
COUNT_BATCH_SIZE = _env_int("SURREAL_COUNT_BATCH_SIZE", 10_000)

# Result handling
EXTERNAL_SORTING_BUFFER_LIMIT = _env_int("SURREAL_EXTERNAL_SORTING_BUFFER_LIMIT", 50_000)
GENERATION_ALLOCATION_LIMIT = _env_int("SURREAL_GENERATION_ALLOCATION_LIMIT", 2**20)

# Caches
TRANSACTION_CACHE_SIZE = _env_int("SURREAL_TRANSACTION_CACHE_SIZE", 10_000)
REGEX_CACHE_SIZE = _env_int("SURREAL_REGEX_CACHE_SIZE", 1_000)

# TPU device-mirror settings (new — no reference analog; this framework's own knobs).
# The *_ONDEVICE_THRESHOLD family below was calibrated against a ~100 ms
# dispatch round trip on a transport that is gone; the values are due for
# re-measurement on a directly attached chip (ROADMAP S2) and have not
# moved since.
TPU_BATCH_MIN_TILE = _env_int("SURREAL_TPU_BATCH_MIN_TILE", 128)
TPU_VECTOR_DTYPE = os.environ.get("SURREAL_TPU_VECTOR_DTYPE", "bfloat16")
TPU_KNN_ONDEVICE_THRESHOLD = _env_int("SURREAL_TPU_KNN_ONDEVICE_THRESHOLD", 4096)
TPU_GRAPH_ONDEVICE_THRESHOLD = _env_int("SURREAL_TPU_GRAPH_ONDEVICE_THRESHOLD", 2048)
# static-shape stabilizer for the graph chain kernels: the frontier pad
# floor, so concurrent chain queries share ONE compiled executable (XLA
# compiles per shape, seconds each). A batched count's lane count is no
# setting: it follows the batch (utils/num.py::count_lanes)
TPU_GRAPH_FRONTIER_PAD = _env_int("SURREAL_TPU_GRAPH_FRONTIER_PAD", 256)
# count-only chains over at least this many total edges skip host hops and
# run the whole chain on device from the seed frontier
TPU_GRAPH_COUNT_EDGES = _env_int("SURREAL_TPU_GRAPH_COUNT_EDGES", 50_000)
# largest per-table node count for the composed dense-matmul count path
# (a 16384^2 bf16 operator is 512MB device-resident)
TPU_GRAPH_DENSE_MAX = _env_int("SURREAL_TPU_GRAPH_DENSE_MAX", 16384)
# corpus size at which `<|k|>` switches from exact search to the IVF ANN
TPU_ANN_MIN_ROWS = _env_int("SURREAL_TPU_ANN_MIN_ROWS", 8192)
TPU_DISABLE = _env_bool("SURREAL_TPU_DISABLE", False)

# Dispatch pipelining (dbs/dispatch.py — the concurrent-query hot path).
# Widest coalesced batch one leader may launch: capped at the largest
# pre-warmed pow2 tile so an oversized queue dispatches as back-to-back
# tiles that REUSE compiled shapes instead of minting a new one (every
# distinct padded width is a separate XLA compile, seconds each).
# Oversized queues chain: the remainder is handed to the
# next leader immediately after this leader's launch phase.
DISPATCH_MAX_WIDTH = _env_int("SURREAL_DISPATCH_MAX_WIDTH", 64)
# batches allowed in flight per bucket (launched, not yet collected):
# depth 2 = classic double buffering (batch N+1 uploads while batch N
# computes/downloads); deeper pipelines help when collect dominates
DISPATCH_PIPELINE_DEPTH = _env_int("SURREAL_DISPATCH_PIPELINE_DEPTH", 2)
# memory-aware split-retry: a transiently-failed batch wider than this is
# BISECTED and the halves retried (recursively) instead of re-executing
# the full width — one oversized launch (RESOURCE_EXHAUSTED) can no
# longer zero out every rider of a 32-wide batch. At or below the floor
# the sub-batch is retried whole, once.
DISPATCH_SPLIT_FLOOR = _env_int("SURREAL_DISPATCH_SPLIT_FLOOR", 4)

# Columnar scan path (idx/column_mirror.py + ops/predicates.py): hot tables'
# scalar fields are mirrored into typed column arrays so a simple WHERE is
# ONE vectorized mask evaluation instead of a per-row cond.compute loop.
COLUMN_MIRROR = _env_bool("SURREAL_COLUMN_MIRROR", True)
# tables below this row count keep the row path (mirror bookkeeping would
# cost more than the scan it replaces)
COLUMN_MIRROR_MIN_ROWS = _env_int("SURREAL_COLUMN_MIRROR_MIN_ROWS", 64)
# widest field set materialized per table; wider tables mirror the first
# N fields seen and predicates on the rest fall back per-row
COLUMN_MIRROR_MAX_FIELDS = _env_int("SURREAL_COLUMN_MIRROR_MAX_FIELDS", 64)
# nested-path materialization depth (`a.b` = 2); deeper lookups fall back
COLUMN_MIRROR_MAX_DEPTH = _env_int("SURREAL_COLUMN_MIRROR_MAX_DEPTH", 2)
# surviving-row block size: docs are fetched and deadlines checked per block
COLUMN_BLOCK_SIZE = _env_int("SURREAL_COLUMN_BLOCK_SIZE", 4096)
# ingest-time debounced rebuild (pattern of GRAPH_PREWARM): a commit into a
# mirrored table arms a timer; when writes quiesce the mirror rebuilds in
# the background so the next query starts fresh. Query-time rebuilds are
# rate-limited by the same window (stale + inside the window = row path).
COLUMN_REBUILD_DEBOUNCE_SECS = _env_float("SURREAL_COLUMN_REBUILD_DEBOUNCE", 0.5)
# lowerable residual WHERE conjuncts of a kNN statement prefilter every
# search strategy, exact and IVF (top-k among matching rows — the
# reference's condition-checker semantics; tests/test_knn_strategies.py
# holds each strategy to it); only the txn overlay merge post-filters
KNN_COLUMN_PREFILTER = _env_bool("SURREAL_KNN_COLUMN_PREFILTER", True)

# Bulk-ingest pipeline v2 (doc/bulk.py + kvs/ds.py GroupCommit).
# Mirror delta-feed: a bulk statement's decoded column blocks append
# straight onto an up-to-date column mirror at commit (under the version/
# snapshot staleness protocol) instead of arming a full re-scan rebuild;
# a delta that cannot apply (schema drift, non-clean base, interleaved
# row-level writes) falls back to the debounced rebuild.
COLUMN_DELTA_FEED = _env_bool("SURREAL_COLUMN_DELTA_FEED", True)
# Group commit: write-transaction commits route through a per-datastore
# coalescer thread that drains all queued commits in one pass — one
# commit-lock hold, combined per-table version bumps and ONE combined
# column-delta application per flush. Durability/visibility semantics are
# UNCHANGED: commit() still returns only after this transaction's backend
# commit (and conflict check) completed; the coalescer batches work, it
# does not defer acknowledgement.
GROUP_COMMIT = _env_bool("SURREAL_GROUP_COMMIT", True)
# how long an idle coalescer thread lingers before exiting (it respawns on
# the next write commit); bounds the per-stream thread churn
GROUP_COMMIT_LINGER_SECS = _env_float("SURREAL_GROUP_COMMIT_LINGER", 0.2)
# widest flush one drain may take (txns beyond it wait for the next pass)
GROUP_COMMIT_MAX_TXNS = _env_int("SURREAL_GROUP_COMMIT_MAX_TXNS", 64)
# Changefeed batching: a bulk op with a changefeed buffers ONE batch entry
# (record ids + the commit's MVCC version) instead of one mutation dict per
# row; SHOW CHANGES expands it reader-side (cf/reader.py).
CHANGEFEED_BATCH = _env_bool("SURREAL_CHANGEFEED_BATCH", True)

# Row-scan deadline amortization: scan_table/scan_range check the statement
# deadline every N rows instead of every row (a monotonic clock read per row
# is measurable GIL-held work on a million-row scan)
SCAN_DEADLINE_INTERVAL = _env_int("SURREAL_SCAN_DEADLINE_INTERVAL", 256)

# Cluster mode (surrealdb_tpu/cluster/): inter-node RPC deadline — a dead
# shard owner surfaces as a per-shard error after this long instead of a
# hung query — and the liveness-probe pump interval per remote node (the
# probe backs off exponentially up to PROBE_MAX while a node stays down).
CLUSTER_RPC_TIMEOUT_SECS = _env_float("SURREAL_CLUSTER_RPC_TIMEOUT", 10.0)
CLUSTER_PROBE_INTERVAL_SECS = _env_float("SURREAL_CLUSTER_PROBE_INTERVAL", 2.0)
CLUSTER_PROBE_MAX_INTERVAL_SECS = _env_float("SURREAL_CLUSTER_PROBE_MAX_INTERVAL", 30.0)
# Replication factor: record writes land on the hash-ring owner plus RF-1
# distinct successors, and scatter reads tolerate up to RF-1 down nodes
# (answers dedup by record id and flag `degraded`). Clamped to the
# membership size; RF=1 restores the r10 single-copy behavior.
CLUSTER_RF = _env_int("SURREAL_CLUSTER_RF", 2)
# Bounded retry policy for IDEMPOTENT internal-channel ops (reads retry,
# writes never double-apply): per-call attempt cap, exponential backoff
# base/cap (jittered), and a per-STATEMENT retry budget shared by every
# scatter the statement fans out.
CLUSTER_RETRY_MAX = _env_int("SURREAL_CLUSTER_RETRY_MAX", 2)
CLUSTER_RETRY_BASE_SECS = _env_float("SURREAL_CLUSTER_RETRY_BASE", 0.05)
CLUSTER_RETRY_MAX_SECS = _env_float("SURREAL_CLUSTER_RETRY_MAX_BACKOFF", 1.0)
CLUSTER_RETRY_BUDGET = _env_int("SURREAL_CLUSTER_RETRY_BUDGET", 4)
# Per-node circuit breaker on the internal channel: this many consecutive
# RPC failures open the breaker (calls fail fast, no socket); after the
# cooldown one half-open trial (or a liveness-probe success) closes it.
CLUSTER_BREAKER_THRESHOLD = _env_int("SURREAL_CLUSTER_BREAKER_THRESHOLD", 3)
CLUSTER_BREAKER_COOLDOWN_SECS = _env_float("SURREAL_CLUSTER_BREAKER_COOLDOWN", 5.0)
# Coordinator admission control: at most MAX_INFLIGHT statements execute
# concurrently; up to ADMIT_QUEUE more wait up to ADMIT_WAIT seconds, and
# everything beyond that sheds fast with a retryable error — overload
# degrades to bounded latency instead of collapse.
CLUSTER_MAX_INFLIGHT = _env_int("SURREAL_CLUSTER_MAX_INFLIGHT", 64)
CLUSTER_ADMIT_QUEUE = _env_int("SURREAL_CLUSTER_ADMIT_QUEUE", 128)
CLUSTER_ADMIT_WAIT_SECS = _env_float("SURREAL_CLUSTER_ADMIT_WAIT", 2.0)
# Elastic membership + convergent repair (cluster/membership.py,
# cluster/repair.py): shard-migration stream batch size (records per
# record_repair RPC), the anti-entropy sweep interval (0 disables the
# supervised background sweep service — sweeps still run on demand via
# repair.sweep_once), and the read-repair in-flight cap (at most this many
# concurrent divergence back-fills; further divergences stay counted but
# wait for the next read or sweep).
CLUSTER_MIGRATE_BATCH = _env_int("SURREAL_CLUSTER_MIGRATE_BATCH", 256)
CLUSTER_ANTIENTROPY_INTERVAL_SECS = _env_float(
    "SURREAL_CLUSTER_ANTIENTROPY_INTERVAL", 0.0
)
CLUSTER_READ_REPAIR_MAX_INFLIGHT = _env_int(
    "SURREAL_CLUSTER_READ_REPAIR_MAX_INFLIGHT", 8
)
# Tombstone GC (cluster/repair.py): DELETE tombstones in the HLC sidecar
# keyspace older than the TTL are swept ONLY after a clean anti-entropy
# pass has covered their range (the delete provably propagated — GC'ing
# earlier could resurrect the record from a stale replica). The interval
# paces the supervised bg:cluster_tombstone_gc service; 0 disables it
# (tombstone_gc_once stays callable on demand).
CLUSTER_TOMBSTONE_TTL_SECS = _env_float("SURREAL_CLUSTER_TOMBSTONE_TTL", 3600.0)
CLUSTER_TOMBSTONE_GC_INTERVAL_SECS = _env_float(
    "SURREAL_CLUSTER_TOMBSTONE_GC_INTERVAL", 0.0
)

# Failpoint fault-injection engine (surrealdb_tpu/faults.py):
# "site=action[:prob][:count],..." spec string + the seed that makes a
# chaos schedule reproducible (None = unseeded).
FAILPOINTS = os.environ.get("SURREAL_FAILPOINTS", "")
FAULTS_SEED = (
    _env_int("SURREAL_FAULTS_SEED", 0)
    if os.environ.get("SURREAL_FAULTS_SEED") is not None
    else None
)

# Structured event timeline (surrealdb_tpu/events.py): bounded ring of
# trace-linked operational state transitions (flaps, breaker trips,
# degraded reads, sheds, failpoint trips, bg stalls/restarts).
EVENTS_CAP = _env_int("SURREAL_EVENTS_CAP", 1024)

# bg service-task supervision (bg.spawn_service(restart=True)): a service
# loop that dies on an UNCAUGHT exception is restarted with exponential
# backoff capped here; a loop that stayed healthy this long resets the
# backoff ladder.
BG_SERVICE_BACKOFF_BASE_SECS = _env_float("SURREAL_BG_SERVICE_BACKOFF_BASE", 0.2)
BG_SERVICE_BACKOFF_MAX_SECS = _env_float("SURREAL_BG_SERVICE_BACKOFF_MAX", 30.0)
BG_SERVICE_HEALTHY_RESET_SECS = _env_float("SURREAL_BG_SERVICE_HEALTHY_RESET", 60.0)

# Changefeeds
CHANGEFEED_GC_INTERVAL_SECS = _env_int("SURREAL_CHANGEFEED_GC_INTERVAL", 10)

# statements slower than this are counted + logged (slow-query reporting)
SLOW_QUERY_THRESHOLD_SECS = _env_float("SURREAL_SLOW_QUERY_THRESHOLD", 1.0)

# pause before a dispatch retry/split-retry re-execution (lets a
# transiently-overloaded device drain; keep small — riders are blocked)
DISPATCH_RETRY_BACKOFF_SECS = _env_float("SURREAL_DISPATCH_RETRY_BACKOFF", 0.2)

# Graph count-kernel prewarm (idx/graph_csr.py): after RELATE ingest into a
# not-yet-mirrored table quiesces for PREWARM_DELAY seconds, build the CSR
# mirrors and background-compile the batched count kernels so the first
# query after ingest doesn't pay the build + XLA-compile cliff.
GRAPH_PREWARM = _env_bool("SURREAL_GRAPH_PREWARM", True)
GRAPH_PREWARM_DELAY_SECS = _env_float("SURREAL_GRAPH_PREWARM_DELAY", 0.5)

# Request-scoped tracing (tracing.py). Recording is on by default; the
# bounded store retains every slow/errored/client-tagged trace and a
# TRACE_SAMPLE fraction of the rest (tail-based sampling).
TRACE_ENABLED = _env_bool("SURREAL_TRACE_ENABLED", True)
TRACE_SAMPLE = _env_float("SURREAL_TRACE_SAMPLE", 0.02)
TRACE_STORE_SIZE = _env_int("SURREAL_TRACE_STORE_SIZE", 512)
TRACE_MAX_SPANS = _env_int("SURREAL_TRACE_MAX_SPANS", 512)

# Workload statistics plane (stats.py + profiler.py). The statement-
# fingerprint store is a bounded LRU: one entry per normalized statement
# shape, oldest-by-use evicted past the cap (evictions counted). The
# always-on sampling profiler wakes PROFILE_HZ times a second and folds
# one sys._current_frames() snapshot per tick; 0 disables the service
# entirely. The default rate is deliberately low; what a tick costs a
# served statement on the chip is not measured.
# PROFILE_MAX_STACKS bounds the distinct folded-stack series (overflow
# folds into a per-thread <overflow> bucket).
STATEMENTS_STORE_SIZE = _env_int("SURREAL_STATEMENTS_STORE_SIZE", 512)
PROFILE_HZ = _env_float("SURREAL_PROFILE_HZ", 7.0)
PROFILE_MAX_STACKS = _env_int("SURREAL_PROFILE_MAX_STACKS", 512)

# Tenant cost-attribution plane (accounting.py). The per-(ns, db) meter
# store is a bounded LRU (TENANT_STORE_SIZE tenants, TENANT_FP_CAP
# fingerprint drill-down entries per tenant). Budgets are OBSERVE-ONLY
# soft limits: a plain float applies to every tenant, "ns:limit[,...]"
# per namespace; a crossing emits tenant.budget_exceeded + bumps
# tenant_budget_breaches{ns} — never enforcement.
TENANT_ACCOUNTING = _env_bool("SURREAL_TENANT_ACCOUNTING", True)
TENANT_STORE_SIZE = _env_int("SURREAL_TENANT_STORE_SIZE", 256)
TENANT_FP_CAP = _env_int("SURREAL_TENANT_FP_CAP", 32)
TENANT_BUDGET_CPU_S = os.environ.get("SURREAL_TENANT_BUDGET_CPU_S", "")
TENANT_BUDGET_DISPATCH_S = os.environ.get("SURREAL_TENANT_BUDGET_DISPATCH_S", "")
TENANT_BUDGET_ROWS = os.environ.get("SURREAL_TENANT_BUDGET_ROWS", "")
TENANT_BUDGET_BYTES = os.environ.get("SURREAL_TENANT_BUDGET_BYTES", "")

# Plan & pipeline cache (dbs/plan_cache.py): fingerprint-keyed cache of
# the front-of-pipeline artifact chain (parsed AST template with literal
# slots, resolved plan route, compiled predicate/stage programs, index
# defs). Correctness is validation-on-serve, never TTL — every serve
# checks schema/index generation, tenant scope, mirror serve state and
# cluster epoch; a PR 15 plan-mix flip evicts the fingerprint. CAP bounds
# the per-datastore entry LRU; MIN_HITS is how many executions a
# fingerprint needs before its artifacts are installed (1 = first sight).
PLAN_CACHE = _env_bool("SURREAL_PLAN_CACHE", True)
PLAN_CACHE_CAP = _env_int("SURREAL_PLAN_CACHE_CAP", 512)
PLAN_CACHE_MIN_HITS = _env_int("SURREAL_PLAN_CACHE_MIN_HITS", 2)

# XLA's persistent compilation cache (device.py): when the environment
# places it, JAX reads the variable itself and the engine sets nothing;
# unset, device.py points it at <checkout>/.jax_cache.
JAX_COMPILATION_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")

# Flight recorder (bg.py + compile_log.py): background-task registry with
# a watchdog that flips tasks to `stalled` past a per-kind deadline, and a
# bounded XLA compile-event log (prewarm vs on-demand attribution).
BG_WATCHDOG = _env_bool("SURREAL_BG_WATCHDOG", True)
BG_WATCHDOG_INTERVAL_SECS = _env_float("SURREAL_BG_WATCHDOG_INTERVAL", 1.0)
BG_WATCHDOG_DEADLINE_SECS = _env_float("SURREAL_BG_WATCHDOG_DEADLINE", 120.0)
BG_REGISTRY_CAP = _env_int("SURREAL_BG_REGISTRY_CAP", 512)
COMPILE_LOG_CAP = _env_int("SURREAL_COMPILE_LOG_CAP", 512)
# Where `python -m scripts.graftcheck` writes the kernel_audit report and
# where bundle.py reads it back as the bundle's kernel_audit section (the
# audit runs as its own pinned-env process, so a file is the handoff).
KERNEL_AUDIT_REPORT = os.environ.get(
    "SURREAL_KERNEL_AUDIT_REPORT", "/tmp/_graftcheck_report.json"
)
# Where `python -m scripts.graftflow` writes the flow_audit report and
# where bundle.py reads it back as the bundle's flow_audit section (same
# file-handoff contract as KERNEL_AUDIT_REPORT; bundle.py falls back to an
# in-process analysis when the file is absent in a repo checkout).
FLOW_AUDIT_REPORT = os.environ.get(
    "SURREAL_FLOW_AUDIT_REPORT", "/tmp/_graftflow_report.json"
)

# Concurrency sanitizer (utils/locks.py): instrumented lock wrappers record
# the lock-acquisition graph, detect order cycles (potential deadlocks) and
# guarded-state mutations without the declared lock. Zero overhead when off:
# the factories hand back raw threading primitives. SANITIZE_OUT dumps the
# observed report as JSON at pytest sessionfinish (the static lock-order
# cross-check in scripts/graftlint consumes it).
SANITIZE = _env_bool("SURREAL_SANITIZE", False)
SANITIZE_OUT = os.environ.get("SURREAL_SANITIZE_OUT")

# --profile equivalent: enable span recording from the environment
PROFILE = _env_bool("SURREAL_PROFILE", False)

# Websocket / server
# largest accepted HTTP request body (model imports carry inline weights)
HTTP_MAX_BODY_SIZE = _env_int("SURREAL_HTTP_MAX_BODY_SIZE", 64 * 1024 * 1024)
WEBSOCKET_MAX_CONCURRENT_REQUESTS = _env_int(
    "SURREAL_WEBSOCKET_MAX_CONCURRENT_REQUESTS", 24
)

# C1M network plane (net/loop.py): selector-based event-loop ingress.
# NET_LOOP picks the ingress: the nonblocking accept/read/write loop
# multiplexing every HTTP + WS socket (default), or the legacy
# thread-per-connection ThreadingHTTPServer (0; TLS always falls back —
# nonblocking TLS handshakes are out of scope). NET_LOOPS shards sockets
# across that many loops; NET_EXECUTORS bounds the worker pool that runs
# fully-decoded requests (the loop itself never executes a statement).
NET_LOOP = _env_bool("SURREAL_NET_LOOP", True)
NET_LOOPS = _env_int("SURREAL_NET_LOOPS", 1)
NET_EXECUTORS = _env_int("SURREAL_NET_EXECUTORS", 8)
# Overload contracts — every bound sheds CLEANLY (counted close, never
# unbounded memory): MAX_CONNS caps concurrently-open sockets (accepts
# beyond it close immediately); HEADER_TIMEOUT bounds how long a
# connection may dribble request headers (slowloris); WRITE_BUF_MAX caps
# a connection's queued-unsent response bytes (a reader that never drains
# gets a backpressure close); READ_SLACK is the header/framing allowance
# on top of HTTP_MAX_BODY_SIZE for the per-connection read buffer.
NET_MAX_CONNS = _env_int("SURREAL_NET_MAX_CONNS", 110_000)
NET_HEADER_TIMEOUT_SECS = _env_float("SURREAL_NET_HEADER_TIMEOUT", 10.0)
NET_WRITE_BUF_MAX = _env_int("SURREAL_NET_WRITE_BUF_MAX", 4 * 1024 * 1024)
NET_READ_SLACK = _env_int("SURREAL_NET_READ_SLACK", 64 * 1024)
# Per-tenant weighted-fair admission (net/qos.py): each (ns, db) gets a
# token bucket (RATE tokens/s refill, BURST capacity; RATE=0 disables
# rate limiting) and an in-flight quota; past either, requests queue
# (up to ADMIT_QUEUE per tenant, then shed) and drain by deficit
# round-robin — each round a tenant earns QUANTUM_MS of estimated
# statement cost scaled by its weight (see net/qos.py:tenant_weight;
# expensive tenants earn less). Internal cluster RPCs ride a dedicated
# class with its own in-flight bound so scatter traffic can't be
# starved by tenants.
NET_QOS = _env_bool("SURREAL_NET_QOS", True)
NET_TENANT_RATE = _env_float("SURREAL_NET_TENANT_RATE", 0.0)
NET_TENANT_BURST = _env_float("SURREAL_NET_TENANT_BURST", 64.0)
NET_TENANT_INFLIGHT = _env_int("SURREAL_NET_TENANT_INFLIGHT", 16)
NET_ADMIT_QUEUE = _env_int("SURREAL_NET_ADMIT_QUEUE", 64)
NET_QOS_QUANTUM_MS = _env_float("SURREAL_NET_QOS_QUANTUM_MS", 5.0)
NET_INTERNAL_INFLIGHT = _env_int("SURREAL_NET_INTERNAL_INFLIGHT", 32)

# Version of the storage format written by this build
STORAGE_VERSION = 1
