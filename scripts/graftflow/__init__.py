"""graftflow — whole-program interprocedural flow analysis for surrealdb_tpu.

The third analysis layer. graftlint (scripts/graftlint) proves file-local
source properties; graftcheck (scripts/graftcheck) audits the compiled IR
of the registered kernels; graftflow closes the gap BETWEEN functions: it
builds a module-qualified call graph over the whole engine (method
dispatch resolved via class attribution, thread hand-offs via the
`bg.spawn*` / `ThreadPoolExecutor.submit` indirection) and proves
properties over every statically-possible path — including interleavings
no test ever executes.

Rules:

- **GF001 static lock-order**: may-hold sets propagate from every
  `locks.Lock/RLock(name)` `with`/`.acquire()` site through the call
  graph; the derived acquires-while-holding edge graph is checked against
  `utils/locks.HIERARCHY` (inversions, same-level nesting, Tarjan cycles).
  An ABBA ordering that no chaos schedule ever interleaves still fails
  the gate. The runtime sanitizer (SURREAL_SANITIZE=1) validates the
  OBSERVED subset of this graph; `--cross-check <dump>` closes the loop
  by asserting observed ⊆ static (soundness self-validation) and reports
  static-but-never-observed edges as interleaving-coverage gaps.
- **GF002 thread-boundary context propagation**: a spawned body
  (bg.spawn/spawn_service/start_thread/timer, pool submit) that
  transitively reads the tracing/telemetry contextvars without explicit
  propagation (`contextvars.copy_context()` or an explicit trace/ctx
  argument) is an orphan-span source — its spans silently detach from
  the arming request's trace.
- **GF003 interprocedural txn escape**: generalizes graftlint GL004 —
  a `ds.transaction()` handle passed into callees must reach
  commit()/cancel() (or escape further) in the callee graph; a handle
  whose every resolved receiver neither finishes nor re-escapes it leaks
  its snapshot on some path.
- **GF004 hot-path blocking reachability**: generalizes graftlint GL005 —
  blocking host sync (`np.asarray`, `.block_until_ready()`,
  `device_get`, `.tolist()`), `time.sleep`, and coordination-lock
  acquisition *transitively reachable* from the dispatch/launch entry
  points are flagged, not just ones textually inside dispatch files.
  Thread boundaries (`bg.spawn*`) stop the traversal — async work does
  not block the pipeline.

Tooling contract (identical to graftlint/graftcheck): `path:line: GFxxx`
findings, inline `# graftflow: disable[-file]=GFxxx` suppressions, a
committed line-number-free baseline (scripts/graftflow/baseline.json via
scripts/baselines.py), seeded-violation fixtures under
tests/fixtures/graftflow/, a tier-1 gate (via `python -m scripts.analysis`),
and a machine-readable `flow_audit` report embedded as debug-bundle
section 11 (surrealdb-tpu-bundle/5).
"""
