"""Cross-query device dispatch coalescing (the PARALLEL seam, SURVEY §2.5).

Role of the reference's PARALLEL 4-stage pipeline (reference:
core/src/dbs/iterator.rs:569-710): where the reference fans one statement's
records OUT over a thread pool, the TPU-first equivalent fans concurrent
queries IN — requests against the same index mirror coalesce into one
batched kernel launch, amortizing the per-dispatch round trip (launch,
execute, download) across every waiting query.

Leader–follower protocol, no batching window (but see `gather` below): the
first request on an idle bucket becomes the leader and immediately dispatches
everything queued (initially just itself). While its batch is on device, later arrivals
enqueue; when the leader finishes its launch phase it hands the bucket to
the next queued request, which dispatches the accumulated batch. Batching
therefore emerges exactly when dispatch latency exceeds arrival spacing — a
lone query pays zero extra latency, and no caller waits longer than its own
batch.

Throughput hardening (the scale-1.0 concurrent-kNN collapse fixes):

- **Bounded width, chained tiles**: a leader drains at most
  cnf.DISPATCH_MAX_WIDTH requests — the largest pre-warmed pow2 tile
  (utils/num.dispatch_tile) — so an oversized queue dispatches as
  back-to-back batches that REUSE compiled kernel shapes instead of minting
  a new XLA executable per odd width. The remainder is promoted immediately
  after this leader's launch phase (chaining), so capping width costs no
  idle bubbles.

- **Pipeline depth > 1**: up to cnf.DISPATCH_PIPELINE_DEPTH batches may be
  in flight per bucket (launched, not yet collected), bounded by a
  semaphore. Depth 2 is classic double buffering — batch N+1's upload and
  launch overlap batch N's device time and download; deeper pipelines keep
  the device fed when collect dominates. This generalizes the old one-
  launcher + unbounded-collect hand-off and removes convoying behind a
  slow leader under sustained multi-client load.

- **A family's own depth, and gathering**: submit(depth=) fixes a bucket's
  depth where the family knows better than the knob: a sweep, whose launch
  costs the device the same whatever its riders, asks for SWEEP_DEPTH (1).
  One deep, sessions in a closed loop ride alternate batches, and a rider
  that arrives a moment after its group's launch waits a whole cycle for
  the next. submit(gather=True) on a one-deep bucket lets the leader wait
  for riders on their way: until the queue is as wide as the batch before
  last, at most the time one launch phase takes (_gather). A lone session
  never waits; a rider that has left costs the wait once. Who asks: the
  column sweep (ops/pipeline.py: the depth alone), and with the gathering
  the sparse count from the rows with one sweep left, the set chain from
  the rows and the dense count with an operator product
  (idx/graph_csr.py). stats() counts how the gathering fares:
  `gather_waits` (a leader found its queue narrower than the batch before
  last, and waited), `gather_met` (of those: the riders came before the
  deadline) and `gather_wait_s` (the seconds waited).

- **Memory-aware split-retry**: a batch that fails transiently
  (RESOURCE_EXHAUSTED and friends) is NOT re-executed at full width.
  Batches wider than cnf.DISPATCH_SPLIT_FLOOR are bisected and the halves
  re-run (recursively, down to the floor), so one oversized launch cannot
  zero out 32 riders — each rider gets its own result or its own error,
  and the device sees geometrically-shrinking launches instead of the same
  overload again. At or below the floor the sub-batch retries once, whole.
  Deterministic errors (bad payload shapes, engine bugs) never re-execute.
  Split-retries run AFTER the bucket hand-off, so a failing batch does not
  convoy the requests behind it.

Consistency note: a batch runs against the LEADER's snapshot of the mirror
(the runner closure it captured). Followers coalesced into that batch may
observe a mirror state captured microseconds earlier than their own submit —
the same committed-state-only guarantee individual mirror reads give.

Two-phase runners (double buffering): a runner may return a CALLABLE instead
of the results list — the callable is the "collect" phase (blocking result
download). The bucket is handed to the next leader right after the launch
phase returns, so the pipeline depth above is measured launch-to-collect.
What a runner has to say about its launch rides on that callable, as
attributes: a dict `launch_labels` (the graph count runners: `lanes`, the
padded lane count, and for a sparse count `sweeps`, the hops its kernel
swept) joins `batch` on every rider's `dispatch_launch` span,
and `outputs`, the device arrays the collect will read, lets the queue wait
for the device itself (each array's `copy_to_host_async()`, then each
array's `block_until_ready()`) before it calls the closure: the collect then splits into `dispatch_ready_wait` (the
kernels in front, this kernel, the runtime's completion latency) and
`dispatch_fetch` (the rest of the copy the launch started, and the decode).
A closure without `outputs` has its whole collect counted as the wait.

The device's books: the queue counts, over all its buckets (the device is
one), the requests `queued` (submitted, not yet drawn into a batch), the
leaders `launching` (inside their runner's launch phase) and the batches
`inflight` (launched, not yet seen ready). Every change of a count first
adds the time since the last change to ONE of four sums, by the counts as
they stood: `fed_s` (a batch is in flight: the device has work as far as
the host knows), else `launching_s` (a leader is in its launch phase:
uploads and look-ups while the device waits, then the jitted call, after
whose return the device may already run while the runner finishes), else
`handoff_s` (requests wait and no leader runs: promotion, wake-up, the
depth semaphore, a gathering leader's wait, the interpreter lock), else `empty_s` (no statement has
reached the queue). The four sums of two stats() snapshots differ by the
wall time between them. A synchronous runner (its result is no callable)
has its whole run counted as `launching_s`, and so has a split-retry's
re-execution; the served strategies of every benchmark cell are two-phase.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence

from surrealdb_tpu import cnf
from surrealdb_tpu.utils import locks as _locks


_STATES = ("fed", "launching", "handoff", "empty")  # dispatch_device_seconds{state}

_TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "RESOURCE_EXHAUSTED",
    "INTERNAL",
    "Connection reset",
    "Broken pipe",
)


def _transient(e: BaseException) -> bool:
    """Device-side failures worth re-execution: an oversized launch
    exhausts device memory, a busy runtime reports UNAVAILABLE.
    Deterministic errors (bad payload shapes, engine bugs) must NOT
    re-execute the batch. Triage on the runtime's status code instead of
    substrings is ROADMAP D9."""
    if type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        return True
    msg = str(e)
    return any(m in msg for m in _TRANSIENT_MARKERS)


def _retry_cause(e: BaseException) -> str:
    """Low-cardinality retry-cause label: the matched transient marker,
    else the exception class."""
    msg = str(e)
    for m in _TRANSIENT_MARKERS:
        if m in msg:
            return m.strip().replace(" ", "_")
    return type(e).__name__


class _Req:
    __slots__ = (
        "payload", "runner", "event", "result", "error", "done",
        "t_submit", "t_done", "trace_ctx", "tenant", "dispatch_no",
    )

    def __init__(self, payload, runner):
        self.payload = payload
        self.runner = runner
        self.event = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        self.done = False  # its event set while it is not: woken to take over bucket leadership
        self.dispatch_no = 0  # `dispatches` as the launch that drew it counted itself
        self.t_submit = _time.perf_counter()  # queue-wait accounting
        self.t_done: float | None = None  # when the leader handed the result out
        # the submitting request's trace position: whoever LEADS the batch
        # re-parents the kernel spans onto every rider here (tracing.py)
        from surrealdb_tpu import accounting, tracing

        self.trace_ctx = tracing.current()
        # the submitting statement's tenant: every rider of a coalesced
        # batch is charged its own share of the batch's device time
        self.tenant = accounting.current_tenant()


# The pipeline depth a family names for a bucket whose launch costs the device
# the same whatever its riders: a sweep of a whole table (ops/pipeline.py:
# 1.440 ms at 1 rider and at 8 over 3.0M rows, PERF.md section 6, PR 40), of
# a graph operator (idx/graph_csr.py: PR 42) or a dense count's operator
# products at its 8 lanes (the same file: PR 45). The statements that arrive
# while a sweep is in flight ride the next one together, where a second sweep
# beside it would take some of them, wait behind the first on the device
# anyway, and cost the host a launch, a collect and a read-back of its own
# under the interpreter lock.
SWEEP_DEPTH = 1

# A launch phase reads its leader's CPU clock where the leader's own trace is
# tagged, and otherwise at most this often a queue: the read is a system call
# on the chip's host, and two round every launch cost a one-session cell 5%
# of its p50 (PERF.md section 6, PR 49). `launch_cpu_s` over `launch_cpu_of_s`
# is the sampled phases' share of work; ten samples a second tell it as well
# as a clock of 10 ms ticks lets anything.
CPU_SAMPLE_EVERY_S = 0.1


class _Bucket:
    __slots__ = ("lock", "queue", "launching", "sem", "depth",
                 "gather", "arrived", "awaiting", "widths", "launches")

    def __init__(self, depth: int, gather: bool = False):
        self.lock = _locks.Lock("dispatch.bucket")
        self.queue: List[_Req] = []
        self.launching = False  # exactly one leader in the launch phase
        self.depth = depth
        # bounds launched-but-not-collected batches (the pipeline depth)
        self.sem = threading.BoundedSemaphore(depth)
        # a one-deep bucket that gathers (DispatchQueue._gather; all under
        # `lock`): the widths of its last two batches, the seconds of its
        # last five launch phases, and whether its leader waits for riders
        self.gather = gather and depth == 1
        self.arrived = threading.Condition(self.lock)
        self.awaiting = False
        self.widths: List[int] = []
        self.launches: List[float] = []

    def launch_s(self) -> float:
        """What a launch phase of this bucket costs the host: the mean of
        its last five without the longest and the shortest (a compile or a
        collection inside one does not count), the shortest of fewer than
        three, 0 before the first."""
        xs = sorted(self.launches)
        xs = xs[1:-1] if len(xs) >= 3 else xs[:1]
        return sum(xs) / len(xs) if xs else 0.0


class DispatchQueue:
    """Per-datastore coalescing queue for batchable device work.

    submit(key, payload, runner) blocks until the request's result is ready.
    `key` identifies a batchable family (same index, same metric/k/...): only
    requests with equal keys share a kernel launch. `runner` is
    runner(payloads: list) -> list of per-payload results; the leader's
    runner executes the whole batch.

    Ctor overrides exist for tests; production reads the cnf knobs
    (SURREAL_DISPATCH_MAX_WIDTH / _PIPELINE_DEPTH / _SPLIT_FLOOR). Width
    and floor are re-read per dispatch; a bucket's pipeline depth is fixed
    when the bucket is first touched.
    """

    def __init__(
        self,
        max_width: Optional[int] = None,
        pipeline_depth: Optional[int] = None,
        split_floor: Optional[int] = None,
    ):
        self._lock = _locks.Lock("dispatch.queue")
        self._buckets: Dict[Hashable, _Bucket] = {}
        self._max_width_override = max_width
        self._depth_override = pipeline_depth
        self._split_floor_override = split_floor
        # counters (tests / INFO FOR observability)
        self.submitted = 0
        self.dispatches = 0
        self.batched = 0  # requests that rode someone else's dispatch
        self.retries = 0  # batch (re-)executions after a transient device error
        self.splits = 0  # transiently-failed batches bisected for retry
        self.failures = 0  # batches that failed permanently (every rider errored)
        self.launch_s = 0.0  # time in runner launch phases (upload + enqueue)
        # the leaders' own CPU (time.thread_time) in the launch phases that
        # were SAMPLED, and those phases' wall time (_launch: CPU_SAMPLE_EVERY_S)
        self.launch_cpu_s = self.launch_cpu_of_s = 0.0
        self._t_cpu_sample = float("-inf")  # when a launch phase last read the CPU clock
        self.collect_s = 0.0  # time in collect phases (the device's wait and the download)
        self.ready_wait_s = 0.0  # of collect_s: until the outputs were ready on the device
        self.fetch_s = 0.0  # of collect_s: from ready to the results as host values
        self.pipeline_wait_s = 0.0  # leaders blocked on the depth semaphore
        self.gather_waits = 0  # leaders that found their queue narrower than the batch before last, and waited (_gather)
        self.gather_met = 0  # of those: the riders came before the deadline
        self.gather_wait_s = 0.0  # the seconds those leaders waited
        self.width_counts: Dict[int, int] = {}  # batch width -> dispatch count
        # the state clock (module docstring): three counts, the stamp of
        # their last change, and the seconds spent in each state
        self._queued = self._launching = self._inflight = 0
        self._t_state = _time.perf_counter()
        self.fed_s = self.launching_s = self.handoff_s = self.empty_s = 0.0
        self._reported = (0.0, 0.0, 0.0, 0.0)  # the four sums as /metrics last got them

    # ------------------------------------------------------------ knobs
    def _max_width(self) -> int:
        w = self._max_width_override
        if w is None:
            w = cnf.DISPATCH_MAX_WIDTH
        return max(int(w), 1)

    def _depth(self) -> int:
        d = self._depth_override
        if d is None:
            d = cnf.DISPATCH_PIPELINE_DEPTH
        return max(int(d), 1)

    def _split_floor(self) -> int:
        f = self._split_floor_override
        if f is None:
            f = cnf.DISPATCH_SPLIT_FLOOR
        return max(int(f), 1)

    def _move(self, queued: int = 0, launching: int = 0, inflight: int = 0) -> float:
        """The state clock's one transition (caller holds self._lock): close
        the running stretch into the sum of the state it was spent in, then
        change the counts. Returns the stamp."""
        now = _time.perf_counter()
        dt, self._t_state = now - self._t_state, now
        if self._inflight > 0:
            self.fed_s += dt
        elif self._launching > 0:
            self.launching_s += dt
        elif self._queued > 0:
            self.handoff_s += dt
        else:
            self.empty_s += dt
        self._queued += queued
        self._launching += launching
        self._inflight += inflight
        return now

    def _device_seconds_due(self) -> Dict[str, float]:
        """What the four sums grew by since /metrics last got them (caller
        holds self._lock; the caller hands it to telemetry outside)."""
        sums = (self.fed_s, self.launching_s, self.handoff_s, self.empty_s)
        due = {st: v - r for st, v, r in zip(_STATES, sums, self._reported)}
        self._reported = sums
        return due

    def _bucket(self, key: Hashable, depth: Optional[int], gather: bool, riders: int = 1) -> _Bucket:
        with self._lock:
            # the queue counters + bucket map are one guarded unit
            # (sanitizer-declared: stats() diffs depend on their atomicity)
            _locks.assert_held(self._lock, "dispatch.counters")
            b = self._buckets.get(key)
            if b is None:
                b = self._buckets[key] = _Bucket(depth or self._depth(), gather)
            self.submitted += riders
            self._move(queued=riders)
            return b

    def submit(
        self, key: Hashable, payload: Any, runner: Callable[[Sequence[Any]], Sequence[Any]],
        depth: Optional[int] = None, gather: bool = False,
    ) -> Any:
        """`depth`: the pipeline depth of `key`'s bucket where the family
        knows better than the knob (fixed when the bucket is first touched).
        A launch whose device cost does not grow with its riders (a sweep)
        asks for SWEEP_DEPTH: a second sweep in flight beside the first
        would only split between two what one serves. `gather` (of a
        one-deep bucket, fixed with it): its leader waits a moment for the
        riders of its group (_gather)."""
        return self.submit_many(key, (payload,), runner, depth=depth, gather=gather)[0]

    def submit_many(
        self, key: Hashable, payloads: Sequence[Any], runner: Callable[[Sequence[Any]], Sequence[Any]],
        depth: Optional[int] = None, gather: bool = False, rode: Optional[List[int]] = None,
    ) -> List[Any]:
        """submit() for one caller's `payloads` under one key: each a rider
        of its own (`submitted` rises by their number, a batch's width
        counts each), all queued at one instant and next to each other, so
        that an idle bucket launches them together and a busy one's next
        leader draws them together; the caller waits for all of them. More
        riders than a batch may hold leave in consecutive batches by the
        queue's own rule (_lead). Returns the results in the payloads'
        order; raises the first rider's error once every rider is done.
        `rode`: a list that receives the number of the dispatch each rider
        rode (`dispatches` as the launch counted it), for a caller that
        tells how many launches its group took."""
        if not payloads:
            return []
        b = self._bucket(key, depth, gather, riders=len(payloads))
        reqs = [_Req(p, runner) for p in payloads]
        with b.lock:
            b.queue.extend(reqs)
            leader = not b.launching
            if leader:
                b.launching = True
            elif b.awaiting:
                b.arrived.notify()
        if leader:
            self._lead(b)
        for req in reqs:
            # promoted: the previous leader handed the bucket over with this
            # rider at the queue's head; it rides the batch we now dispatch
            while not req.done:
                req.event.wait()
                if not req.done:
                    req.event.clear()
                    self._lead(b)
        if rode is not None:
            rode.extend(r.dispatch_no for r in reqs)
        self._woken(reqs)
        for req in reqs:
            if req.error is not None:
                raise req.error
        return [r.result for r in reqs]

    @staticmethod
    def _woken(reqs: List[_Req]) -> None:
        """The submitter's way out: in its trace the `dispatch_wake` span
        from the leader's hand-out of the last of its riders to this
        thread's running again (a rider's wake-up; for a leader, its bucket
        chores)."""
        last = max(reqs, key=lambda r: r.t_done or 0.0)
        if last.t_done is not None and last.trace_ctx is not None:
            from surrealdb_tpu import tracing

            tracing.record_span_into(
                last.trace_ctx, "dispatch_wake", {},
                last.t_done, _time.perf_counter() - last.t_done,
            )

    def _lead(self, b: _Bucket) -> None:
        """Dispatch ONE width-capped batch (containing this leader's
        request), then hand the bucket to the next queued request — bounding
        every caller's latency to its own batch even under sustained load.
        The launch phase releases the bucket, so the next batch uploads
        while up to `depth` earlier batches compute/download; the depth
        semaphore is what keeps the pipeline from running away."""
        t_sem = _time.perf_counter()
        b.sem.acquire()  # blocks while `depth` batches are in flight
        waited = _time.perf_counter() - t_sem
        try:
            with b.lock:
                gathered = self._gather(b) if b.gather else None
                width = min(len(b.queue), self._max_width())
                batch, b.queue = b.queue[:width], b.queue[width:]
                if b.gather:
                    b.widths = (b.widths + [width])[-2:]
            finish = self._launch(batch, b, waited, gathered) if batch else None
            with b.lock:
                if b.queue:
                    nxt = b.queue[0]
                    nxt.event.set()  # launching stays True; nxt owns the bucket
                else:
                    b.launching = False
            # post-hand-off phase: collect the two-phase results, or
            # split-retry a transiently-failed batch — either way the next
            # leader is already launching
            if finish is not None:
                finish()
        finally:
            b.sem.release()

    @staticmethod
    def _gather(b: _Bucket) -> Optional[tuple]:
        """The leader of a one-deep bucket, the device free, waits for the
        riders of its group (caller holds b.lock). One deep, sessions in a
        closed loop ride alternate batches: while one batch is in flight the
        riders of the batch before it are on their way back, and one that
        arrives a moment after the launch rides the next batch, a whole
        cycle (launch, kernel, hand-over) later: the tail of a cell whose
        kernel is shorter than its riders' way back. So the leader waits
        until the queue is as wide as the batch before last was, at most one
        launch phase (_Bucket.launch_s): where a batch costs the
        same at any width, a rider is worth that wait, since the launch of
        its own it would otherwise need costs the host as much. A rider
        that does not come costs the wait once: the width that launched is
        what the batch after next expects. A lone session never waits.
        Returns (the seconds waited, whether the riders came) for the
        queue's `gather_*` counters, None where there was nothing to wait
        for."""
        want = b.widths[0] if len(b.widths) == 2 else 0
        if len(b.queue) >= want:
            return None
        t0 = _time.perf_counter()
        deadline = t0 + b.launch_s()
        b.awaiting = True
        try:
            while len(b.queue) < want:
                left = deadline - _time.perf_counter()
                if left <= 0 or not b.arrived.wait(left):
                    break
        finally:
            b.awaiting = False
        return _time.perf_counter() - t0, len(b.queue) >= want

    def _charge_batch(self, batch: List[_Req], elapsed: float, meter: str) -> None:
        """Tenant accounting: split one batch phase's elapsed time EQUALLY
        across its riders — the shares sum exactly to the launch_s /
        collect_s increment the same phase added, so per-tenant dispatch
        meters conserve against stats() by construction. Runs with no
        dispatch lock held (accounting.store must never nest inside)."""
        from surrealdb_tpu import accounting

        if not batch:
            return
        share = elapsed / len(batch)
        for r in batch:
            ns, db = r.tenant if r.tenant is not None else (None, None)
            accounting.charge(ns, db, **{meter: share})

    def _trace_batch(
        self, batch: List[_Req], name: str, start: float, dur: float,
        error=None, cpu: Optional[float] = None, **extra,
    ) -> None:
        """Stamp one kernel-phase span onto EVERY rider's trace, parented
        at the span each request was in when it submitted — a query that
        rode someone else's launch still shows its dispatch level. Once a
        trace position: a statement's riders that share a batch
        (submit_many) share the span. `cpu`: the CPU seconds the calling
        thread, the batch's leader, burned in the phase: they go onto the
        copy in the leader's own trace, if that trace is tagged, and onto no
        rider's (a copy without `cpu_ms` reads "slept through it"). The
        leader's own `dispatch_ready_wait` carries what of the device's wait
        its thread ran after all (a backend that runs the program on the
        waiting thread: all of it)."""
        from surrealdb_tpu import tracing

        labels = {"batch": len(batch), **extra}
        own = tracing.current() if cpu is not None else None
        if own is not None and not own.trace.explicit:
            own = None
        for ctx in {id(r.trace_ctx): r.trace_ctx for r in batch}.values():
            tracing.record_span_into(
                ctx, name, labels, start, dur, error, cpu=cpu if ctx is own else None
            )

    def _launch(
        self, batch: List[_Req], b: _Bucket, pipeline_wait: float,
        gathered: Optional[tuple],
    ) -> Optional[Callable[[], None]]:
        """Phase 1: run the leader's runner. Sync runners finish here;
        two-phase runners return the collect closure to run after the
        bucket hand-off. A transient launch failure also returns a closure
        (the split-retry), so the hand-off never waits on re-execution.
        `gathered`: what this batch's leader waited for its group (_gather)."""
        from surrealdb_tpu import telemetry, tracing

        with self._lock:
            _locks.assert_held(self._lock, "dispatch.counters")
            self.dispatches += 1
            for r in batch:
                r.dispatch_no = self.dispatches
            self.batched += len(batch) - 1
            self.pipeline_wait_s += pipeline_wait
            if gathered is not None:
                self.gather_waits += 1
                self.gather_wait_s += gathered[0]
                self.gather_met += gathered[1]
            self.width_counts[len(batch)] = self.width_counts.get(len(batch), 0) + 1
            now = self._move(queued=-len(batch), launching=1)
            own = tracing.current()  # the leader's own trace position: its riders' too
            sampled = (own is not None and own.trace.explicit) or now - self._t_cpu_sample >= CPU_SAMPLE_EVERY_S
            if sampled:
                self._t_cpu_sample = now
        payloads = [r.payload for r in batch]
        runner = batch[0].runner

        t0 = _time.perf_counter()
        cpu0 = _time.thread_time() if sampled else None  # the leader's CPU clock, beside launch_s's
        telemetry.observe_hist("dispatch_batch_size", len(batch))
        telemetry.observe("dispatch_pipeline_wait", pipeline_wait)
        if pipeline_wait >= 0.001:
            # only a BLOCKED leader earns a span node: an uncontended
            # acquire would bury every trace under microsecond noise
            self._trace_batch(
                batch, "dispatch_pipeline_wait", t0 - pipeline_wait,
                pipeline_wait, depth=b.depth,
            )
        from surrealdb_tpu import accounting

        traced = set()
        for r in batch:
            telemetry.observe("dispatch_queue_wait", t0 - r.t_submit)
            if id(r.trace_ctx) not in traced:  # a statement's riders of one batch queued together
                traced.add(id(r.trace_ctx))
                tracing.record_span_into(
                    r.trace_ctx, "dispatch_queue_wait", {"batch": len(batch)},
                    r.t_submit, t0 - r.t_submit,
                )
            ns, db = r.tenant if r.tenant is not None else (None, None)
            accounting.charge(
                ns, db,
                dispatch_wait_s=t0 - r.t_submit, dispatch_batches=1,
            )
        from surrealdb_tpu import compile_log

        res = due = None
        try:
            # detached: the leader thread's own trace must not swallow the
            # kernel spans — they are stamped onto every rider below. An
            # on-demand XLA compile inside the launch is attributed to the
            # FIRST rider's trace (compile_log.attribution): exactly one
            # trace carries the compile span, the rest see a cache hit.
            # The failpoint sits INSIDE the transient/deterministic triage:
            # an injected `error-transient` exercises the real bisect-retry
            # machinery, an injected plain error the rider fail-out.
            with tracing.detached(), compile_log.attribution(
                batch[0].trace_ctx
            ), telemetry.span(
                "dispatch_launch"
            ), telemetry.trace_annotation("dispatch_launch"):
                from surrealdb_tpu import faults

                faults.fire("dispatch.launch")
                res = runner(payloads)
        except Exception as e:
            # transient device-side failures (RESOURCE_EXHAUSTED on an
            # oversized launch) split-retry AFTER the bucket hand-off
            # instead of re-executing the full width / convoying the next
            # batch
            if not _transient(e):
                self._fail(batch, e, t0)
                return None
            self._count_retry(batch, e, t0)
            err = e  # bind: `e` is unbound once the except block exits
            return lambda: self._split_retry(batch, err)
        except BaseException as e:  # propagate to every waiter
            self._fail(batch, e, t0)
            return None
        finally:
            elapsed = _time.perf_counter() - t0
            cpu = _time.thread_time() - cpu0 if sampled else None
            if b.gather:
                with b.lock:
                    b.launches = (b.launches + [elapsed])[-5:]
            with self._lock:
                _locks.assert_held(self._lock, "dispatch.counters")
                self.launch_s += elapsed
                if sampled:
                    self.launch_cpu_s += cpu
                    self.launch_cpu_of_s += elapsed
                # a collect closure came back: the batch is on the device.
                # Anything else (results, a failure) ends the dispatch here
                landed = callable(res)
                self._move(launching=-1, inflight=int(landed))
                if not landed:
                    due = self._device_seconds_due()
            if due is not None:
                telemetry.inc_each("dispatch_device_seconds", "state", due)
            # charge riders the SAME elapsed launch_s just accumulated
            # (success and failure paths both) — conservation holds exactly
            self._charge_batch(batch, elapsed, "dispatch_s")
        self._trace_batch(
            batch, "dispatch_launch", t0, _time.perf_counter() - t0, cpu=cpu,
            **getattr(res, "launch_labels", {}),
        )
        if not callable(res):
            self._distribute(batch, res)
            return None

        outputs = getattr(res, "outputs", None)

        def collect() -> None:
            t1 = _time.perf_counter()
            cpu1 = tracing.cpu_now()  # the leader's own trace, if tagged
            t_ready = cpu_ready = None
            try:
                try:
                    with tracing.detached(), compile_log.attribution(
                        batch[0].trace_ctx
                    ), telemetry.span(
                        "dispatch_collect"
                    ), telemetry.trace_annotation("dispatch_collect"):
                        if outputs is not None:
                            # the ready stamp: wait for the device on the
                            # arrays themselves, then read them back. This IS
                            # the collect phase, after the bucket's hand-off.
                            # The copies to the host start first (a no-op
                            # where the launch started them), behind the
                            # kernel: the read then finds the values on the
                            # host and gives the interpreter lock up no
                            # second time (0.7 ms a time under eight sessions)
                            for a in outputs:
                                a.copy_to_host_async()
                            for a in outputs:
                                a.block_until_ready()  # graftlint: disable=GL005
                            with self._lock:
                                _locks.assert_held(self._lock, "dispatch.counters")
                                t_ready = self._move(inflight=-1)
                            cpu_ready = tracing.cpu_since(cpu1)
                        results = res()
                finally:
                    # before any triage below: a split-retry's re-execution
                    # is outside collect_s and keeps its own books
                    with self._lock:
                        _locks.assert_held(self._lock, "dispatch.counters")
                        fetched = t_ready is not None
                        t2 = self._move(inflight=0 if fetched else -1)
                        if not fetched:
                            # no `outputs`, or the wait itself failed: the
                            # whole collect was the wait
                            t_ready = t2
                        elapsed = t2 - t1
                        self.collect_s += elapsed
                        self.ready_wait_s += t_ready - t1
                        self.fetch_s += t2 - t_ready
                        due = self._device_seconds_due()
                    telemetry.inc_each("dispatch_device_seconds", "state", due)
                    telemetry.observe("dispatch_ready_wait", t_ready - t1)
                    if fetched:
                        telemetry.observe("dispatch_fetch", t2 - t_ready)
                    self._charge_batch(batch, elapsed, "dispatch_s")
            except Exception as e:
                if not _transient(e):
                    self._fail(batch, e, t1)
                    return
                self._count_retry(batch, e, t1)
                self._split_retry(batch, e)
                return
            except BaseException as e:
                self._fail(batch, e, t1)
                return
            cpu = tracing.cpu_since(cpu1)
            # the whole collect was the wait: so was its CPU
            self._trace_batch(
                batch, "dispatch_ready_wait", t1, t_ready - t1, cpu=cpu_ready if fetched else cpu
            )
            if fetched:
                self._trace_batch(batch, "dispatch_fetch", t_ready, t2 - t_ready)
            self._trace_batch(batch, "dispatch_collect", t1, _time.perf_counter() - t1, cpu=cpu)
            self._distribute(batch, results)

        return collect

    # ------------------------------------------------------------ retry
    def _run_whole(self, sub: List[_Req]) -> Sequence[Any]:
        """One full re-execution (launch + collect) of a sub-batch. The
        re-run's time is charged to the riders as dispatch_retry_s —
        deliberately NOT dispatch_s, which conserves against launch_s +
        collect_s (re-executions are extra device time outside both)."""
        from surrealdb_tpu import compile_log, tracing

        payloads = [r.payload for r in sub]
        with self._lock:
            _locks.assert_held(self._lock, "dispatch.counters")
            t0 = self._move(launching=1)  # the state clock: as a synchronous run
        try:
            with tracing.detached(), compile_log.attribution(sub[0].trace_ctx):
                res = sub[0].runner(payloads)
                return res() if callable(res) else res
        finally:
            with self._lock:
                _locks.assert_held(self._lock, "dispatch.counters")
                t1 = self._move(launching=-1)
            self._charge_batch(sub, t1 - t0, "dispatch_retry_s")

    def _split_retry(self, batch: List[_Req], cause: BaseException) -> None:
        """Memory-aware recovery from a transient batch failure: bisect
        down to the split floor so every rider gets its OWN outcome and no
        re-execution repeats the width that just overloaded the device.
        Runs after the bucket hand-off — concurrent with the next leader."""
        from surrealdb_tpu import telemetry

        floor = self._split_floor()
        _time.sleep(cnf.DISPATCH_RETRY_BACKOFF_SECS)

        def rec(sub: List[_Req], err: BaseException) -> None:
            if len(sub) <= floor:
                # at the floor: one whole retry, then give up on this slice
                t0 = _time.perf_counter()
                try:
                    results = self._run_whole(sub)
                except BaseException as e2:
                    e2.__cause__ = err
                    self._fail(sub, e2, t0)
                    return
                self._trace_batch(
                    sub, "dispatch_retry", t0, _time.perf_counter() - t0,
                    cause=_retry_cause(err),
                )
                self._distribute(sub, results)
                return
            mid = len(sub) // 2
            with self._lock:
                _locks.assert_held(self._lock, "dispatch.counters")
                self.splits += 1
            telemetry.inc("dispatch_splits", cause=_retry_cause(err))
            self._trace_batch(
                batch=sub, name="dispatch_split", start=_time.perf_counter(),
                dur=0.0, cause=_retry_cause(err), halves=f"{mid}+{len(sub) - mid}",
            )
            for half in (sub[:mid], sub[mid:]):
                t1 = _time.perf_counter()
                try:
                    results = self._run_whole(half)
                except Exception as e2:
                    if _transient(e2):
                        # still overloaded: back off and keep bisecting —
                        # only THIS half's riders ride the recursion
                        self._count_retry(half, e2, t1)
                        _time.sleep(cnf.DISPATCH_RETRY_BACKOFF_SECS)
                        rec(half, e2)
                    else:
                        e2.__cause__ = err
                        self._fail(half, e2, t1)
                    continue
                except BaseException as e2:
                    e2.__cause__ = err
                    self._fail(half, e2, t1)
                    continue
                self._trace_batch(
                    half, "dispatch_retry", t1, _time.perf_counter() - t1,
                    cause=_retry_cause(err),
                )
                self._distribute(half, results)

        rec(batch, cause)

    def _count_retry(self, batch: List[_Req], e: BaseException, start: float) -> None:
        from surrealdb_tpu import telemetry

        with self._lock:
            _locks.assert_held(self._lock, "dispatch.counters")
            self.retries += 1
        telemetry.inc("dispatch_retries", cause=_retry_cause(e))
        # the cause rides as a LABEL, not a span error: a retried-then-
        # successful request is not errored and must not be pinned as such
        self._trace_batch(
            batch, "dispatch_transient", start, _time.perf_counter() - start,
            cause=_retry_cause(e),
        )

    def _distribute(self, batch: List[_Req], results: Sequence[Any]) -> None:
        if len(results) != len(batch):
            self._fail(
                batch,
                RuntimeError(
                    f"dispatch runner returned {len(results)} results "
                    f"for {len(batch)} requests"
                ),
            )
            return
        t_done = _time.perf_counter()
        for r, res in zip(batch, results):
            r.result = res
            r.done = True
            r.t_done = t_done
            r.event.set()

    def _fail(self, batch: List[_Req], e: BaseException, start: Optional[float] = None) -> None:
        from surrealdb_tpu import telemetry

        with self._lock:
            _locks.assert_held(self._lock, "dispatch.counters")
            self.failures += 1
        telemetry.inc("dispatch_failures", error=telemetry.error_class(e))
        t = _time.perf_counter()
        self._trace_batch(
            batch, "dispatch_fail", start if start is not None else t,
            t - start if start is not None else 0.0,
            error=telemetry.error_class(e),
        )
        for r in batch:
            r.error = e
            r.done = True
            r.event.set()

    def stats(self) -> Dict[str, float]:
        """Scalar counters only — consumers diff these numerically (slow-
        query records, the benchmark's `dispatch.*` counter readers). The
        state clock's running stretch is closed up to now first, so the
        four `*_s` state sums of two snapshots differ by the wall time
        between them. Beside what the device was given stands what the
        interpreter was given, in CPU seconds of `time.thread_time()`:
        `launch_cpu_s` (what the leaders ran themselves in the launch phases
        that read the clock: those of tagged requests and one in
        CPU_SAMPLE_EVERY_S; `launch_cpu_of_s` is those phases' wall time, and
        the rest of it their leaders slept or waited for the interpreter)
        and, process-wide, `cpu_exec_s` (the bg:net_exec
        workers) and `cpu_loop_s` (the bg:net_loop threads), summed from the
        slots those threads write (telemetry.cpu_seconds). The executor
        calls this twice a statement, so it reads no CPU clock: what a
        worker or a loop has burned since its last reading (at most a
        quarter second and one task or pass: telemetry.CPU_SLOT_EVERY_S) is
        missing from the sums until its next."""
        from surrealdb_tpu import telemetry

        cpu = telemetry.cpu_seconds()
        with self._lock:
            self._move()
            return {
                "submitted": self.submitted,
                "dispatches": self.dispatches,
                "batched": self.batched,
                "retries": self.retries,
                "splits": self.splits,
                "failures": self.failures,
                "launch_s": round(self.launch_s, 4),
                "launch_cpu_s": self.launch_cpu_s,
                "launch_cpu_of_s": self.launch_cpu_of_s,
                "cpu_exec_s": cpu.get("exec", 0.0),
                "cpu_loop_s": cpu.get("loop", 0.0),
                "collect_s": round(self.collect_s, 4),
                "pipeline_wait_s": round(self.pipeline_wait_s, 4),
                "gather_waits": self.gather_waits,
                "gather_met": self.gather_met,
                "gather_wait_s": self.gather_wait_s,
                "ready_wait_s": self.ready_wait_s,
                "fetch_s": self.fetch_s,
                "fed_s": self.fed_s,
                "launching_s": self.launching_s,
                "handoff_s": self.handoff_s,
                "empty_s": self.empty_s,
            }

    def width_distribution(self) -> Dict[int, int]:
        """{batch width: dispatch count} since startup. Diff two snapshots
        to attribute a measurement window (the benchmark's `correct` line
        reads it, and the bundle's `engine` section carries it)."""
        with self._lock:
            return dict(self.width_counts)
