"""Device-resident CSR graph mirrors + batched frontier expansion.

Role of the reference's per-record edge-prefix scans (reference:
core/src/dbs/processor.rs:610-701 collect_edges, sql/value/get.rs:404-446 —
hop N over R records ⇒ R separate KV range scans) re-designed TPU-first
(SURVEY §3.5): each (src_table, direction, foreign_table) pointer keyspace is
packed into CSR arrays (indptr/indices) over a node id space shared across
all mirrors of a database, so a multi-hop idiom like `->knows->person` is a
sequence of fixed-shape gather kernels with on-device dedup instead of
R₁+R₂+… pointer chases.

Maintenance is incremental: the base adjacency is built with ONE scan over
the source table's `~` keyspace (all directions/foreign-tables at once), and
every committed RELATE/DELETE applies per-edge deltas through the
transaction's graph-delta buffer (kvs/tx.py) — no corpus rescans on write
(reference analog: trees/store/cache.rs generation swap, improved). Device
arrays are recompacted lazily from the host adjacency when dirty; queries
inside a transaction that has its own uncommitted edge writes fall back to
the exact KV walk (sql/path.py graph_hop).
"""

from __future__ import annotations

import contextvars
import math
import threading
import time as _time
from surrealdb_tpu.utils import locks as _locks
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from surrealdb_tpu import key as keys, telemetry
from surrealdb_tpu.dbs.dispatch import SWEEP_DEPTH
from surrealdb_tpu.key.encode import prefix_end
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.utils.byte_cache import ByteBudgetCache
from surrealdb_tpu.utils.num import count_lane_set, count_lanes, next_pow2 as _next_pow2, path_slots


class NodeInterner:
    """Thing ↔ dense-int mapping shared by every mirror of one (ns, db)."""

    def __init__(self):
        self.id_of: Dict[Tuple[str, str], int] = {}
        self.node_of: List[Thing] = []
        self._lock = _locks.Lock("idx.graph.interner")

    def __len__(self) -> int:
        return len(self.node_of)

    def intern(self, t: Thing) -> int:
        k = (t.tb, repr(t.id))
        i = self.id_of.get(k)
        if i is None:
            with self._lock:
                i = self.id_of.get(k)
                if i is None:
                    i = len(self.node_of)
                    self.node_of.append(t)
                    self.id_of[k] = i
        return i

    def lookup(self, t: Thing) -> Optional[int]:
        return self.id_of.get((t.tb, repr(t.id)))


def _csc_arrays(src: np.ndarray, dst: np.ndarray, cap: int):
    """Edges `src -> dst` over `cap` nodes as the arrays chain_count_batch
    sweeps: `csrc`, the sources in destination order (stable), padded to
    utils/num.py::path_slots with the sentinel `cap` (the one place that
    pads this axis: shape keys, warm-up and audit read the arrays), and
    `cptr` [cap + 1], the bounds of each destination's bin in it (pad slots
    lie past the last bin)."""
    csrc = np.full(path_slots(src.size), cap, dtype=np.int32)
    csrc[: src.size] = src[np.argsort(dst, kind="stable")]
    cptr = np.zeros(cap + 1, dtype=np.int32)
    np.cumsum(np.bincount(dst, minlength=cap), out=cptr[1:])
    return cptr, csrc


class PointerCsr:
    """Adjacency for one (src_tb, direction, foreign_tb) pointer keyspace.

    Host side: `adj` dict of global-int lists — authoritative, updated by
    deltas. Device side: indptr/indices arrays compacted lazily.
    """

    def __init__(self, interner: NodeInterner):
        self.interner = interner
        self.adj: Dict[int, List[int]] = {}
        self.version = 0  # bumped on every mutation (dense-operator cache key)
        self.dirty = True
        self.indptr: Optional[np.ndarray] = None
        self.indices: Optional[np.ndarray] = None
        self._dev = None  # (jnp indptr, jnp indices) cache
        self._dev_csc = None  # (jnp cptr, jnp csrc) dst-sorted cache
        self.edge_count = 0
        self.n_built = 0
        self.max_degree = 0
        self._lock = _locks.Lock("idx.graph.mirror")

    def load(self, adj: Dict[int, List[int]]) -> None:
        with self._lock:
            _locks.assert_held(self._lock, "graph.adjacency")
            self.adj = adj
            self.edge_count = sum(len(v) for v in adj.values())
            self.version += 1
            self.dirty = True

    def apply(self, src: int, dst: int, add: bool) -> None:
        """Idempotent delta: pointer keys are unique in KV, so the mirror
        holds at most one (src, dst) entry per keyspace."""
        with self._lock:
            # adjacency/version/dirty are one guarded unit: a mutation
            # outside idx.graph.mirror races ensure_arrays' compaction
            _locks.assert_held(self._lock, "graph.adjacency")
            lst = self.adj.setdefault(src, [])
            if add:
                if dst not in lst:
                    lst.append(dst)
                    self.edge_count += 1
            else:
                try:
                    lst.remove(dst)
                    self.edge_count -= 1
                except ValueError:
                    pass
                if not lst:
                    del self.adj[src]
            self.version += 1
            self.dirty = True

    def ensure_arrays(self) -> None:
        """Compact host adjacency into CSR arrays (numpy only — no KV)."""
        n = len(self.interner)
        with self._lock:
            _locks.assert_held(self._lock, "graph.adjacency")
            if not self.dirty and self.n_built == n and self.indptr is not None:
                return
            t0 = _time.perf_counter()
            # indptr spans a pow2-padded node capacity and indices a pow2
            # buffer so XLA kernel shapes stay stable while edges trickle in
            # (a recompile per RELATE would dwarf the gather itself)
            cap = _next_pow2(max(n, 1))
            indptr = np.zeros(cap + 1, dtype=np.int32)
            for src, lst in self.adj.items():
                if src < n:
                    indptr[src + 1] = len(lst)
            self.max_degree = int(indptr.max()) if n else 0
            np.cumsum(indptr, out=indptr)
            indices = np.zeros(_next_pow2(max(int(indptr[-1]), 1)), dtype=np.int32)
            fill = indptr[:-1].copy()
            for src, lst in self.adj.items():
                if src >= n:
                    continue
                k = fill[src]
                indices[k : k + len(lst)] = lst
            self.indptr = indptr
            self.indices = indices
            self._dev = None
            self._dev_csc = None
            self.n_built = n
            self.dirty = False
            telemetry.stage(
                "graph_csr_build", t0, _time.perf_counter() - t0,
                bytes=indptr.nbytes + indices.nbytes,
            )

    def host_arrays(self):
        """(indptr, indices) of one compaction: ensure_arrays swaps the two
        under the mirror's lock, so they are read under it, together."""
        self.ensure_arrays()
        with self._lock:
            return self.indptr, self.indices

    def device_arrays(self):
        import jax.numpy as jnp

        self.ensure_arrays()
        if self._dev is None:
            self._dev = (jnp.asarray(self.indptr), jnp.asarray(self.indices))
        return self._dev

    def device_csc(self):
        """Destination-sorted (cptr, csrc) device arrays for scatter-free
        dense SpMV hops (batched count chains): y[v] = Σ x[src] over edges
        into v becomes a prefix sum over dst-sorted x[csrc] + a boundary
        gather — gathers and a prefix-scan only, no scatter (TPU scatter-add
        is serial-slow; _kernels' prefix_sum_rows + gather ride the VPU).
        Padding edges carry the sentinel src `cap` and lie past every real
        bin. These are the record-level operands, over the id space persons
        and edge records share: what chain_count_batch sweeps for a chain
        that is not one of composable `->edge->node` pairs
        (GraphMirrors._csc_pair builds the same arrays over one table's
        compact ids for those)."""
        import jax.numpy as jnp

        self.ensure_arrays()
        if self._dev_csc is None:
            t0 = _time.perf_counter()
            cap = len(self.indptr) - 1
            nnz = int(self.indptr[-1])
            cptr, csrc = _csc_arrays(
                np.repeat(np.arange(cap, dtype=np.int32), np.diff(self.indptr)),
                self.indices[:nnz], cap,
            )
            t1 = _time.perf_counter()
            telemetry.stage(
                "graph_csc_build", t0, t1 - t0, bytes=cptr.nbytes + csrc.nbytes,
                paths=nnz, slots=csrc.size,
            )
            self._dev_csc = (jnp.asarray(cptr), jnp.asarray(csrc))
            # the transfer is asynchronous: the stage times the host's
            # hand-off, and its bytes say what the first kernel to read the
            # arrays waits for inside its collect
            telemetry.stage(
                "graph_csc_upload", t1, _time.perf_counter() - t1,
                bytes=sum(a.nbytes for a in self._dev_csc),
            )
        return self._dev_csc


def _served(
    form: str, t_enter: Optional[float], operand: Optional[str] = None, filter: str = "none",
    first_hop: Optional[str] = None,
) -> None:
    """One count chain served by `form` (`dense`, `csc` or `host`): the
    `graph_count_form` counter and the `form` label of the statement's
    `graph_prepare` span come from this one argument, so they cannot
    disagree. A `csc` count also says which `operand` its kernel swept
    (`composed`: node->node operators in a table's compact id space;
    `records`: the record-level mirrors in the shared id space), to the
    `graph_csc_operand` counter and the span's `operand` label alike; a
    `csc` count that launches says where the frontier after its first hop
    came from (`first_hop`, to the `graph_csc_first_hop` counter's `how` and
    the span's label: `rows`, read on the host from the first operator's
    source-sorted rows, so the kernel sweeps one hop less; `sweep`, the
    kernel swept for it from the seeds); and
    every count says what became of a predicate on the chain (`filter`, to
    the `graph_count_filter` counter's `route` and the span's label: `none`,
    the chain has no WHERE; `fused`, the predicate on its final node part
    rode the count as a mask over the node table's column mirror; `host`,
    a chain with a WHERE that fell back to the KV walk). The
    span closes here, at the dispatch submit (for a count no dispatcher
    carries: where its fused chain starts): hop specs, frontier, work
    estimate, the dense form's refusal, operand look-ups (and, on a first
    statement, the builds inside them), the first hop's rows since
    chain_count's entry. A `host`
    count is known for one only when its walk has ended, so there the span
    holds the whole count; a reader of preparation time leaves it out."""
    telemetry.inc("graph_count_form", form=form)
    telemetry.inc("graph_count_filter", route=filter)
    labels = {"form": form, "filter": filter}
    if operand is not None:
        telemetry.inc("graph_csc_operand", operand=operand)
        labels["operand"] = operand
    if first_hop is not None:
        telemetry.inc("graph_csc_first_hop", how=first_hop)
        labels["first_hop"] = first_hop
    if t_enter is not None:
        telemetry.stage(
            "graph_prepare", t_enter, _time.perf_counter() - t_enter, **labels
        )


# ------------------------------------------------------------------ kernels
_JITTED: dict = {}


def _dense_shape_key(lanes: int, fsz: int, n0: int, As, weighted: bool = False) -> tuple:
    """Compile-cache key of the dense count kernel: lane count, frontier
    pad, source space + each operator's padded dims (what XLA keys on), and
    a `w` where the count ends in per-rider weights: another program."""
    key = (lanes, fsz, n0, tuple(tuple(int(d) for d in a.shape) for a in As))
    return key + ("w",) if weighted else key


def _csc_shape_key(lanes: int, fsz: int, n_cap: int, csc_hops, last_hop, weighted: bool = False) -> tuple:
    """Compile-cache key of the batched CSC count kernel: per-hop array
    paddings decide the executable shape (and, as the dense key, whether
    the count ends in per-rider weights)."""
    key = (
        lanes, fsz, n_cap,
        tuple(int(a.shape[0]) for hop in csc_hops for pair in hop for a in pair),
        tuple(int(p.shape[0]) for (p,) in last_hop),
    )
    return key + ("w",) if weighted else key


def _stack_lanes(payloads, fsz: int, pad: int):
    """One coalesced batch of (frontier, weights) payloads as two
    [lanes, fsz] int32 arrays, the lane count chosen from the riders
    (utils/num.py::count_lanes: 8, 16, 32 or 64 at the dispatcher's width
    cap of 64): batches of 1 to 8 share one compiled executable, and a
    kernel's gathers, prefix sums and tables are as wide as the batch
    needs, not as wide as the widest batch. Padding lanes carry zero
    weights."""
    bp = count_lanes(len(payloads))
    frs = np.full((bp, fsz), pad, dtype=np.int32)
    cws = np.zeros((bp, fsz), dtype=np.int32)
    for i, p in enumerate(payloads):
        frs[i] = p[0]
        cws[i] = p[1]
    return frs, cws


def _lane_end_weights(payloads, lanes: int, at: int = 2) -> tuple:
    """The riders' end weights (a payload's third member: one device array
    a rider, cached a predicate binding by GraphMirrors._end_weights), one a
    lane; for a set chain the riders' masks (`at` 1: _reach_mask). A padding
    lane carries no seed, so whatever it ends in counts nothing: it borrows
    the first rider's array."""
    ws = [p[at] for p in payloads]
    return tuple(ws + [ws[0]] * (lanes - len(ws)))


def _weights_into(cptr: np.ndarray, csrc: np.ndarray, passing: np.ndarray, size: int) -> np.ndarray:
    """End weights of a count whose last `->edge->node` pair keeps only the
    destinations `passing` (local ids): w[v] = the pair's paths from v to
    one of them, read off the pair's destination-sorted paths (`cptr`,
    `csrc`: _csc_arrays) bin by bin, so the cost follows the paths that
    pass, not the operator. int32 [size]."""
    starts = cptr[passing].astype(np.int64)
    return np.bincount(csrc[_slots(starts, cptr[passing + 1] - starts)], minlength=size).astype(np.int32)


def _collect_counts(out, riders: int, lanes: int, sweeps: Optional[int] = None):
    """The collect phase of a batched count launched at `lanes` lanes: the
    riders' counts off the device. The `graph_count_lanes` counter and the
    `lanes` label the dispatcher puts on every rider's `dispatch_launch`
    span come from this one argument (the pattern of _served): riders over
    lanes is the fill. A sparse count's launch also says how many hops its
    kernel swept (`sweeps`). `outputs` hands the dispatcher the array to
    wait for, so the collect's time splits into the device's and the
    read-back."""
    telemetry.inc("graph_count_lanes", lanes=lanes)

    def collect():
        return np.asarray(out)[:riders].tolist()

    collect.launch_labels = {"lanes": lanes} if sweeps is None else {"lanes": lanes, "sweeps": sweeps}
    collect.outputs = (out,)
    return collect


def _reach_shape_key(lanes: int, fsz: int, n_cap: int, csc_hops, walk_pad: int = 0) -> tuple:
    """Compile-cache key of the batched set kernel (chain_reach_batch): the
    lanes, the frontier's pad, the node space and each swept operand's
    paddings. Every rider is masked, so the key has no ending. With a
    `walk_pad` it is the key of the program that reads the one hop from
    the operator's rows (`csc_hops` then holds its `(indptr, dst)`)."""
    key = (lanes, fsz, n_cap, tuple(int(a.shape[0]) for hop in csc_hops for pair in hop for a in pair))
    return key + ("rows", walk_pad) if walk_pad else key


def _pack_mask(mask: np.ndarray, n_cap: int) -> np.ndarray:
    """pred(node) over a table's compact ids as chain_reach_batch reads it:
    uint32 [ceil(n_cap / 32)], bit v % 32 of word v // 32 for node v."""
    bits = np.zeros(-(-n_cap // 32) * 32, dtype=bool)
    bits[: mask.size] = mask
    return np.packbits(bits, bitorder="little").view("<u4")


def _ring_ids(ring: np.ndarray) -> np.ndarray:
    """The nodes of one ring as chain_reach_batch hands it back, ascending:
    bit-packed words (uint32, _pack_mask's layout) from a sweep, or a walk
    read from the rows (int32: a destination a slot, a node once a walk
    that ends at it, -1 in the slots that hold none)."""
    if ring.dtype == np.int32:
        ids = np.unique(ring)
        return ids[ids >= 0]
    return np.flatnonzero(np.unpackbits(np.ascontiguousarray(ring, dtype="<u4").view(np.uint8), bitorder="little"))


def _reached(
    form: str, t_enter: float, t_ready: float, filter: str = "none", operand: Optional[str] = None,
    depth: int = 0, memo: str = "fill", ids: int = 0, rings: int = 0, last_hop: Optional[str] = None,
) -> None:
    """One `array::distinct(<chain>)` served, as _served says it of a count:
    the `graph_reach` counter (`form`: `csc`, the device's sweep of composed
    operators, or `host`, the hop-by-hop set walk or the KV walk; `filter`:
    `none`, `fused` or `host` as a count's; `operand`: `composed` for `csc`)
    and the labels of the expression's `graph_prepare` span come from this
    one argument list. The span runs from the expression's entry to
    `t_ready` (the dispatch submit of the expression that fills the
    statement's ring memo; the look-up's end for one the memo serves; the
    walk's end for `host`) and is written when the rings are back, so it can
    say `ids` (the nodes this expression returned) and `rings` (the hops the
    statement's one program kept) beside `depth` (this chain's pairs) and
    `memo` (`fill`: this expression ran the chain, for itself and for the
    statement's others; `hit`: it read their rings). The expression that
    fills a `csc` run which launches also says where its last hop came
    from (`last_hop`, as a count's `first_hop`: `rows`, the kernel read the
    frontier's rows of the operator, bounded by the operators' walk pad;
    `sweep`, it swept every slot of the operators; the counter says `none`
    for every other expression)."""
    telemetry.inc("graph_reach", form=form, filter=filter, operand=operand or "none", last_hop=last_hop or "none")
    labels = {"form": form, "filter": filter, "depth": depth, "memo": memo, "ids": ids, "rings": rings}
    if operand is not None:
        labels["operand"] = operand
    if last_hop is not None:
        labels["last_hop"] = last_hop
    telemetry.stage("graph_prepare", t_enter, t_ready - t_enter, **labels)


def _grouped(
    t_enter: float, t_ready: float, rows: int, riders: int, families: int, launches: int, filter: str, depth: int,
    last_hop: Optional[str] = None,
) -> None:
    """One chain family of a statement served for all its rows together
    (GraphMirrors.reach_group): the `graph_reach_group` counter and the
    statement's `graph_reach_group` span come from this one argument list,
    as _reached's do. The span runs from the group's entry to its submit
    (`t_ready`: operators, mask, every row's frontier; what follows is the
    dispatcher's) and is written when the rings are back, so it can say
    `riders` (the rows that brought a frontier to the sweep) and `launches`
    (the dispatches those riders rode: 1 with the bucket idle) beside
    `rows` and `families` (the chains the statement's fill serves this way,
    a span each). `rows` and `riders` grow with a statement's result, so they
    are labels of the span alone; the counter keeps what is bounded. The
    family's `graph_prepare` span says `memo=fill` once here, not once a
    row: the rows' expressions then say `hit`."""
    telemetry.inc("graph_reach_group", form="csc", filter=filter, depth=depth, launches=min(launches, 4))
    telemetry.stage(
        "graph_reach_group", t_enter, t_ready - t_enter, rows=rows, riders=riders, families=families,
        launches=launches, form="csc", filter=filter, depth=depth,
    )
    _reached("csc", t_enter, t_ready, filter, "composed", depth=depth, memo="fill", ids=0, rings=0, last_hop=last_hop)


def _collect_rings(out, riders: int, lanes: int, slots: int):
    """The collect phase of a batched set chain launched at `lanes` lanes:
    each rider's rings off the device, uint32 [hops, words] a rider (int32
    [1, walk_pad], its walk's destinations, where the kernel read the rows).
    `lanes` and `slots` (the operand slots the kernel swept a lane, all
    hops together; the walk pad's slots where it read the rows) join the
    riders' `dispatch_launch` spans, as _collect_counts' labels do;
    `outputs` is what the dispatcher waits for."""

    def collect():
        return list(np.asarray(out)[:riders])

    collect.launch_labels = {"lanes": lanes, "slots": slots}
    collect.outputs = (out,)
    return collect


def _local_seeds(inv: dict, frontier: np.ndarray, counts: np.ndarray, fsz: int, pad: int):
    """A chain's weighted seeds in a table's compact ids ([fsz] int32 each,
    pad slots at the sentinel `pad` with weight 0) and how many seeds the
    table holds: a seed outside it starts no path of the chain."""
    fr = np.full(fsz, pad, dtype=np.int32)
    cw = np.zeros(fsz, dtype=np.int32)
    j = 0
    for g, c in zip(frontier.tolist(), counts.tolist()):
        loc = inv.get(int(g))
        if loc is not None:
            fr[j] = loc
            cw[j] = c
            j += 1
    return fr, cw, j


def _local_ids(space: dict, size: int) -> np.ndarray:
    """table_space()'s global->local map as an array over `size` global ids
    (-1: not a node of the table), for whole-array look-ups."""
    g = np.asarray(space["globals"], dtype=np.int64)
    inv = np.full(max(size, int(g.max(initial=-1)) + 1), -1, dtype=np.int64)
    inv[g] = np.arange(g.size)
    return inv


def _slots(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Every slot of the ranges `[starts[i], starts[i] + lens[i])`, range
    after range: the running slot number less its range's first is the
    offset inside the range."""
    return np.repeat(starts - (np.cumsum(lens) - lens), lens) + np.arange(int(lens.sum()))


# The longest pad a composed operator's rows are read at. The kernel densifies
# a frontier with one scatter update a (lane, pad slot), ~9 ns each on a v5e
# (PERF.md section 6, PR 42): at 4,096 that is 0.3 ms at 8 lanes beside the
# 3.2 ms of the sweep it saves; a pad of 65,536 costs more than the sweep.
ROW_PAD_MAX = 4096


def _row_pad(longest: int) -> int:
    """The size a composed operator's rows pad to when a count reads its
    first hop from them: the power of two at or above the longest row, or
    0 (no such read: every count sweeps from its seeds) where a hub's row
    passes ROW_PAD_MAX."""
    pad = _next_pow2(max(longest, 1))
    return pad if pad <= ROW_PAD_MAX else 0


def _walk_pad(first: dict, second: dict) -> int:
    """The pad of the longest two-step walk through the composed operators
    `first` then `second`: over `first`'s sources, the lengths of the
    `second` rows of its row's destinations summed, a destination once a
    path, padded as a row is (_row_pad: 0 where a walk passes
    ROW_PAD_MAX, or where `first` has a row too long to be a frontier).
    A property of the two operators and of no rider: every set rider of
    a generation reads its last hop at this one size. Kept on `first`
    while `second` is the generation it was reckoned from."""
    gen, got = (second["key"], second["gen"]), first.get("walk_pad")
    if got is None or got[0] != gen:
        pad = 0
        if first["row_pad"]:
            (indptr, dst), rows = first["by_src"], second["by_src"][0]
            run = np.concatenate([[0], np.cumsum(np.diff(rows)[dst], dtype=np.int64)])
            pad = _row_pad(int((run[indptr[1:]] - run[indptr[:-1]]).max()))
        first["walk_pad"] = got = (gen, pad)
    return got[1]


def _seed_rows(op: dict, fr: np.ndarray, cw: np.ndarray):
    """The weighted frontier one hop past the seeds `fr` (local ids, weights
    `cw`), read from the composed operator `op`'s source-sorted rows
    (`by_src`: `indptr`, `dst`; _csc_pair): every seed's row with the
    seed's weight on each entry, row after row, as two [row_pad] int32
    arrays like _local_seeds' (pad slots at the sentinel `n_pad`, weight
    0). A destination two paths reach stands twice: the kernel's
    densifying scatter adds them, and int32 sums wrap the same in any
    order. Whole-array NumPy: one gather of the rows' slots. None where
    the rows together are longer than `row_pad`."""
    (indptr, dst), pad = op["by_src"], op["row_pad"]
    starts = indptr[fr]
    lens = indptr[fr + 1] - starts
    total = int(lens.sum())
    if total > pad:
        return None
    out = np.full(pad, op["n_pad"], dtype=np.int32)
    w = np.zeros(pad, dtype=np.int32)
    out[:total] = dst[_slots(starts, lens)]
    w[:total] = np.repeat(cw, lens)
    return out, w


def _compose_coo(ip1, ix1, ip2, ix2, space_src: dict, space_dst: dict, max_paths: int):
    """src -> mid -> dst through two CSR mirrors over the shared id space, as
    COO `(local_src, local_dst)` in the two tables' compact ids: one entry a
    2-hop path, a repeated path a repeated entry, in source order (the
    first mirror's edges stand by global source id, and a table's compact
    ids rise with its global ids: table_space). Whole-array NumPy (the
    second mirror's degrees repeat the first mirror's edges), so a million
    paths are a few array passes. None past `max_paths`, told from the
    degrees before any path is laid out."""
    n1, n2 = len(ip1) - 1, len(ip2) - 1
    inv_s = _local_ids(space_src, max(n1, n2))
    inv_d = inv_s if space_dst is space_src else _local_ids(space_dst, max(n1, n2))
    ls = inv_s[np.repeat(np.arange(n1), np.diff(ip1))]
    mid = ix1[: int(ip1[-1])].astype(np.int64)
    ok = (ls >= 0) & (mid < n2)
    ls, mid = ls[ok], mid[ok]
    start = ip2[mid].astype(np.int64)
    deg = ip2[mid + 1] - start
    total = int(deg.sum())
    if total > max_paths:
        return None
    # path k of m1-edge e reads ix2[start[e] + k]
    ld = inv_d[ix2[_slots(start, deg)]]
    keep = ld >= 0
    return np.repeat(ls, deg)[keep], ld[keep]


def _kernels():
    """Lazily build the jitted hop kernels (keeps jax off the import path).

    The whole remaining chain compiles into ONE jitted call: per-hop
    kernels made a 3-hop query ~7 dispatch round trips; fused it is one.
    """
    if _JITTED:
        return _JITTED["chain"]
    import jax
    import jax.numpy as jnp

    def gather_hop(ptr, idx, frontier, weights, md):
        # one weighted CSR gather: frontier [F] ints with multiplicities →
        # neighbor slots [F*md] + per-slot weight (0 = padding). Carrying a
        # count per node instead of a bare frontier makes the hop an SpMV
        # over the adjacency, which preserves the reference's flatten-
        # without-dedup result multiplicity (sql/value/get.rs:404-446)
        # while still deduplicating the *frontier* between hops.
        n = ptr.shape[0] - 1
        fr = jnp.clip(frontier, 0, jnp.maximum(n - 1, 0))
        s = ptr[fr]
        deg = ptr[fr + 1] - s
        offs = jnp.arange(md)[None, :]
        take = jnp.clip(s[:, None] + offs, 0, idx.shape[0] - 1)
        valid = (offs < deg[:, None]) & (weights > 0)[:, None] & (frontier < n)[:, None]
        w = jnp.where(valid, weights[:, None], 0)
        return idx[take].reshape(-1), w.reshape(-1)

    def accum_cap(nodes, w, n_nodes, out_size):
        # dense scatter-add dedup: per-node path counts survive the frontier
        # compaction (capped, jit-static output size)
        safe = jnp.where(w > 0, jnp.clip(nodes, 0, n_nodes), n_nodes)
        dense = jnp.zeros(n_nodes + 1, dtype=jnp.int32).at[safe].add(w)
        dense = dense.at[n_nodes].set(0)
        present = jnp.nonzero(dense > 0, size=out_size, fill_value=n_nodes)[0]
        return present, jnp.where(present < n_nodes, dense[present], 0)

    def chain_impl(hops, frontier, weights, mds, n_cap, out_sizes, count_only):
        frj, cwj = frontier, weights
        last = len(hops) - 1
        for h, mirrors in enumerate(hops):
            if h == last and count_only:
                # the final hop of a count never materializes neighbors:
                # paths through node v multiply by deg(v), so the count is
                # one weighted degree reduction (no gather, no scatter —
                # the batched form stays tiny at any frontier width)
                total = 0
                for (ptr, _idx), _md in zip(mirrors, mds[h]):
                    n = ptr.shape[0] - 1
                    fr_c = jnp.clip(frj, 0, jnp.maximum(n - 1, 0))
                    deg = ptr[fr_c + 1] - ptr[fr_c]
                    valid = (frj < n) & (cwj > 0)
                    total = total + jnp.where(valid, deg * cwj, 0).sum()
                return total
            pieces, ws = [], []
            for (ptr, idx), md in zip(mirrors, mds[h]):
                nodes, w = gather_hop(ptr, idx, frj, cwj, md)
                pieces.append(nodes)
                ws.append(w)
            allnodes = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
            allw = jnp.concatenate(ws) if len(ws) > 1 else ws[0]
            frj, cwj = accum_cap(allnodes, allw, n_cap, out_sizes[h])
        return frj, cwj

    @partial(
        jax.jit, static_argnames=("mds", "n_cap", "out_sizes", "count_only")
    )
    def chain_kernel(hops, frontier, weights, mds, n_cap, out_sizes, count_only):
        """Full multi-hop chain in one dispatch. hops: tuple (one per hop) of
        tuples of (indptr, indices) device arrays (one per contributing
        mirror); mds/out_sizes: matching static pow2 paddings. count_only
        skips the final compaction and returns the scalar path count."""
        return chain_impl(hops, frontier, weights, mds, n_cap, out_sizes, count_only)

    def _deg(ptr, frj, cwj):
        """Weighted degree reduction: Σ cw[v]·deg(v) over a compact frontier."""
        n = ptr.shape[0] - 1
        fr_c = jnp.clip(frj, 0, jnp.maximum(n - 1, 0))
        deg = ptr[fr_c + 1] - ptr[fr_c]
        return jnp.where((frj < n) & (cwj > 0), deg * cwj, 0).sum(axis=-1)

    SCAN_ROW = 128  # the longest row of prefix_sum_rows: an int32 tile's minor axis

    def prefix_sum_rows(vals):
        """Inclusive int32 prefix sum along axis 1 of `vals` [lanes, slots],
        wrapping as int32 addition does (any re-association gives the same
        bits). `jnp.cumsum` lowers on a TPU to a reduce-window within each
        row of 128 slots, which XLA tiles one element a step whenever it
        keeps the rows in its fast memory (2.1 ms a hop at SNB SF3's 8 x
        1,179,648; PERF.md section 6, PR 34 and PR 37). So the rows are
        scanned here as XLA lays them out for that step anyway, the in-row
        positions on the major axis: the row totals (one reduction over the
        slabs of [lanes, rows]), their exclusive prefix (a [lanes, rows]
        operand, where a reduce-window is cheap), and one pass over the
        slabs with a running sum that starts from it. A row is 128 slots,
        an int32 tile's minor axis, and shorter only where that would be
        more slabs than rows (under 16,384 slots: the power of two under the
        square root, so a small operand takes few steps); slots that are no
        multiple of it are padded with zeros here and cut off again."""
        lanes, slots = vals.shape
        row = min(SCAN_ROW, 1 << (math.isqrt(slots).bit_length() - 1))
        rows = -(-slots // row)
        vals = jnp.pad(vals, ((0, 0), (0, rows * row - slots)))
        slabs = vals.reshape(lanes, rows, row).transpose(2, 0, 1)
        totals = slabs.sum(axis=0)
        before = jnp.cumsum(totals, axis=1) - totals

        def step(run, slab):
            run = run + slab
            return run, run

        _, out = jax.lax.scan(step, before, slabs)
        return out.transpose(1, 2, 0).reshape(lanes, rows * row)[:, :slots]

    def sweep(x, mirrors, n_cap):
        """One hop of a dense frontier `x` [lanes, n_cap + 1] (sentinel
        column n_cap) over the destination-sorted operands `mirrors`
        ((cptr, csrc), ...): y[v] = the sum of x over the sources of the
        edges into v, as a gather at the sources, a prefix sum and the
        differences at the bin bounds. [lanes, n_cap]."""
        x = x.at[:, n_cap].set(0)
        zcol = jnp.zeros((x.shape[0], 1), dtype=jnp.int32)
        y = 0
        for cptr, csrc in mirrors:
            vals = x[:, csrc]  # sentinel src reads the zeroed column
            s = jnp.concatenate([zcol, prefix_sum_rows(vals)], axis=1)
            y = y + (s[:, cptr[1:]] - s[:, cptr[:-1]])
        return y

    @partial(jax.jit, static_argnames=("n_cap",))
    def chain_count_batch(csc_hops, last_hop, frontiers, weights, n_cap, end_weights=None):
        """Batched count-only chains for B concurrent queries over the SAME
        adjacency (the cross-query coalescing seam, dbs/dispatch.py).
        Scatter-free: TPU scatter-add is serial-slow and vmapped
        nonzero/compaction is worse, so every hop is cast as dense SpMV in
        prefix-sum form —
        - seeds densify with one tiny scatter (B x frontier-width updates)
        - each non-final hop: gather counts at dst-sorted edge sources,
          prefix-scan, difference at bin boundaries (y[v] = S[end_v] -
          S[start_v]) — gathers + one prefix_sum_rows, VPU-friendly at any
          width
        - the final hop of a count never materializes neighbors: it is a
          degree dot-product, or, where the chain's final node part has a
          predicate, a dot-product with the rider's own `end_weights` (one
          [n_cap] array a lane, `last_hop` then empty: w[v] = the last
          pair's paths from v to a node that passes; the bare count is the
          case w = deg, and runs the program it always ran)
        csc_hops: tuple per non-final hop of ((cptr, csrc), ...);
        last_hop: ((ptr,), ...). The operands are either composed
        node->node operators in one table's compact ids (_csc_pair: a hop
        an `->edge->node` pair, n_cap the padded table) or the record-level
        mirrors in the shared id space (PointerCsr.device_csc: a hop a
        spec, n_cap the padded interner); the kernel cannot tell."""
        B = frontiers.shape[0]
        ends = None if end_weights is None else jnp.stack(end_weights)
        if not csc_hops and ends is not None:
            # one filtered pair: the seeds' own end weights
            fr_c = jnp.clip(frontiers, 0, n_cap - 1)
            w = jnp.take_along_axis(ends, fr_c, axis=1)
            return jnp.where((frontiers < n_cap) & (weights > 0), w * weights, 0).sum(axis=1)
        if not csc_hops and not last_hop:
            return jnp.zeros((B,), dtype=jnp.int32)
        if not csc_hops:
            # 1-hop count: weighted degree over the compact seed frontier
            total = 0
            for (ptr,) in last_hop:
                n = ptr.shape[0] - 1
                fr_c = jnp.clip(frontiers, 0, jnp.maximum(n - 1, 0))
                deg = ptr[fr_c + 1] - ptr[fr_c]
                total = total + jnp.where(
                    (frontiers < n) & (weights > 0), deg * weights, 0
                ).sum(axis=1)
            return total
        # densify the seed frontier: [B, n_cap+1] (sentinel column n_cap)
        lane_off = (jnp.arange(B) * (n_cap + 1))[:, None]
        safe = jnp.where(weights > 0, jnp.clip(frontiers, 0, n_cap), n_cap)
        x = (
            jnp.zeros(B * (n_cap + 1), dtype=jnp.int32)
            .at[(lane_off + safe).reshape(-1)]
            .add(weights.reshape(-1))
            .reshape(B, n_cap + 1)
        )
        zcol = jnp.zeros((B, 1), dtype=jnp.int32)
        for mirrors in csc_hops:
            x = jnp.concatenate([sweep(x, mirrors, n_cap), zcol], axis=1)
        xr = x[:, :n_cap]
        if ends is not None:
            return (xr * ends).sum(axis=1)
        total = 0
        for (ptr,) in last_hop:
            deg = ptr[1 : n_cap + 1] - ptr[:n_cap]
            total = total + (xr * deg[None, :]).sum(axis=1)
        return total

    def reach_rows(indptr, dst, frontiers, masks, n_cap, walk_pad):
        """chain_reach_batch's ONE swept hop read from the operator's
        source-sorted rows (`indptr` [n_cap + 1], `dst` in source order,
        padded past the last row: _reach_plan), where the pair of operators
        bounds every rider's walk by `walk_pad` slots (_walk_pad): the
        frontier's rows laid end to end, a destination a slot. A lane: the
        rows' first slots and lengths (0 at the sentinel n_cap), their
        running sum over the frontier; for output slot t the row it falls
        in is the last whose first running slot is at or below t, so the
        slot of `dst` to read is t plus `starts - before` of that row,
        which telescopes into one compare-and-sum over the frontier (the
        differences of `starts - before`, each where its row starts at or
        below t; int32 sums wrap back whatever the order) with the frontier
        on the major axis and the slots on the lanes: no search, no scatter,
        no prefix sum over the operator's slots, no pass over the node
        space. Then the destination and its mask bit. Returns int32 [B,
        walk_pad]: the destination where the slot lies inside the walk and
        its node passes, else -1, a destination once a walk (the collect
        makes the set: _ring_ids)."""
        fr = jnp.clip(frontiers, 0, n_cap)
        starts = indptr[fr]
        lens = indptr[jnp.minimum(fr + 1, n_cap)] - starts
        run = jnp.cumsum(lens, axis=1)
        before = run - lens
        base = starts - before
        step = jnp.concatenate([base[:, :1], base[:, 1:] - base[:, :-1]], axis=1)
        t = jnp.arange(walk_pad, dtype=jnp.int32)
        at = t[None, :] + jnp.where(before[:, :, None] <= t[None, None, :], step[:, :, None], 0).sum(axis=1)
        ids = dst[jnp.clip(at, 0, dst.shape[0] - 1)]
        inside = t[None, :] < run[:, -1:]
        safe = jnp.where(inside, ids, 0)
        word = jnp.take_along_axis(jnp.stack(masks), safe >> 5, axis=1)
        passes = (word >> (safe & 31).astype(jnp.uint32)) & 1
        return jnp.where(inside & (passes > 0), ids, -1)

    @partial(jax.jit, static_argnames=("n_cap", "walk_pad"))
    def chain_reach_batch(csc_hops, frontiers, masks, n_cap, walk_pad=0):
        """Batched SET chains for B concurrent statements over the same
        composed operators (`array::distinct(<chain>)`: the nodes a walk of
        exactly h pairs reaches, each once), the set form of
        chain_count_batch: the frontiers densify to {0, 1}, every hop is
        the count's sweep with the result clamped back to {0, 1} (so no sum
        passes the slots of an operator, whatever the walks number), and
        EVERY hop's frontier is kept, where a count keeps a sum of the last
        alone. `frontiers` [B, pad] local ids (pad slots at the sentinel
        n_cap); `masks`: one uint32 [ceil(n_cap / 32)] array a lane, bit
        v % 32 of word v // 32 set where node v passes the rider's
        predicate (all of them for a chain without one). Returns uint32
        [B, hops, words]: the nodes after each hop that pass, bit-packed
        the same way, so a rider reads back n_cap / 8 bytes a hop whatever
        it reached. With a `walk_pad` (static: another program under the
        same XLA module name, so trace readers find whichever served the
        set) `csc_hops` is the ONE hop's operator by source, `(((indptr,
        dst),),)`, and the hop is read from the frontier's rows
        (reach_rows: int32 [B, 1, walk_pad] comes back, the one ring a
        walk)."""
        if walk_pad:
            (((indptr, dst),),) = csc_hops
            return reach_rows(indptr, dst, frontiers, masks, n_cap, walk_pad)[:, None, :]
        B = frontiers.shape[0]
        lane_off = (jnp.arange(B) * (n_cap + 1))[:, None]
        x = (
            jnp.zeros(B * (n_cap + 1), dtype=jnp.int32)
            .at[(lane_off + jnp.clip(frontiers, 0, n_cap)).reshape(-1)]
            .set(1)
            .reshape(B, n_cap + 1)
        )
        zcol = jnp.zeros((B, 1), dtype=jnp.int32)
        words = -(-n_cap // 32)
        shifts = jnp.arange(32, dtype=jnp.uint32)
        rings = []
        for mirrors in csc_hops:
            reached = sweep(x, mirrors, n_cap) > 0
            bits = jnp.pad(reached, ((0, 0), (0, words * 32 - n_cap))).reshape(B, words, 32)
            rings.append((bits.astype(jnp.uint32) << shifts).sum(axis=2, dtype=jnp.uint32))
            x = jnp.concatenate([reached.astype(jnp.int32), zcol], axis=1)
        return jnp.stack(rings, axis=1) & jnp.stack(masks)[:, None, :]

    @partial(jax.jit, static_argnames=("n0",))
    def chain_count_batch_dense(As, outdeg, frontiers, weights, n0, end_weights=None):
        """Batched count chains as MXU matmuls: each logical `->edge->node`
        pair is pre-composed into a dense node->node operator (bf16, exact
        for multiplicities < 256), so B concurrent 3-hop counts are TWO
        matmuls + a degree dot-product in ONE dispatch. Counts ride as
        int32: per operator the frontier splits into four unsigned 8-bit
        limbs stacked on the row axis ([4B, n]: 32 MXU rows at the 8 lanes
        of a batch of up to 8 riders, 256 at 64),
        ONE bf16 x bf16 -> float32 product (both operands exact in bf16),
        and an int32 shift-and-add recombination. A float32 accumulator
        holds at most 255 x the operator's largest column sum, which
        _dense_pair keeps under 2**24, so every product is exact and the
        int32 sums wrap as chain_count_batch's do: the two forms agree bit
        for bit. (The XLA module name keeps the `jit_chain_count_batch`
        prefix: trace readers find whichever form served the count.)
        seeds arrive as compact LOCAL ids. A count whose final node part
        has a predicate ends in `end_weights` (one array a lane over the
        last pair's source space, `outdeg` then None), as
        chain_count_batch's does."""
        B = frontiers.shape[0]
        lane = (jnp.arange(B) * (n0 + 1))[:, None]
        safe = jnp.where(weights > 0, jnp.clip(frontiers, 0, n0), n0)
        x = (
            jnp.zeros(B * (n0 + 1), dtype=jnp.int32)
            .at[(lane + safe).reshape(-1)]
            .add(weights.reshape(-1))
            .reshape(B, n0 + 1)[:, :n0]
        )
        for A in As:
            limbs = jnp.concatenate(
                [jax.lax.shift_right_logical(x, jnp.int32(8 * k)) & 0xFF for k in range(4)]
            ).astype(jnp.bfloat16)
            y = jnp.dot(limbs, A, preferred_element_type=jnp.float32)
            y = y.astype(jnp.int32).reshape(4, B, -1)
            x = y[0] + (y[1] << 8) + (y[2] << 16) + (y[3] << 24)
        ends = outdeg[None, :] if end_weights is None else jnp.stack(end_weights)
        return (x * ends).sum(axis=1)

    _JITTED["chain"] = chain_kernel
    _JITTED["chain_count_batch"] = chain_count_batch
    _JITTED["chain_reach_batch"] = chain_reach_batch
    _JITTED["prefix_sum_rows"] = jax.jit(prefix_sum_rows)
    _JITTED["chain_count_batch_dense"] = chain_count_batch_dense
    return chain_kernel


_UNFUSED = object()  # a filtered count no composed operator can carry: the KV walk


class _EndFilter:
    """The predicate on a count chain's final node part, for one statement:
    `compiled` (ops/predicates.py, its constants bound) over table `tb`,
    answered from the table's column mirror as this reader may see it.
    Every look-up may say None: the mirror is stale or being written by
    this transaction, a referenced column holds values the mask cannot
    judge, or a path is unknown to it; the count then takes the KV walk."""

    def __init__(self, gm: "GraphMirrors", ctx, tb: str, compiled):
        self.gm, self.ctx, self.tb, self.compiled = gm, ctx, tb, compiled
        self.ns, self.db = ctx.ns_db()
        self._mirror = self._mask = False  # not looked up yet

    def mirror(self):
        if self._mirror is False:
            registry = getattr(self.ctx.ds(), "column_mirrors", None)
            m = None if registry is None else registry.serveable(self.ctx, (self.ns, self.db, self.tb))
            self._mirror = m if m is not None and m.n else None
        return self._mirror

    def local_mask(self) -> Optional[np.ndarray]:
        """pred(node) over `tb`'s compact ids (GraphMirrors.table_space). A
        node with no record behind it answers as a record of NONEs does,
        which is what the KV walk fetches there."""
        if self._mask is False:
            self._mask = self._local_mask()
        return self._mask

    def _local_mask(self) -> Optional[np.ndarray]:
        from surrealdb_tpu.idx.column_mirror import _all_none_column

        mirror = self.mirror()
        cols = None if mirror is None else mirror.columns_for(self.compiled.paths)
        if cols is None:
            return None
        mask, needs_row = self.compiled.evaluate(cols)
        if needs_row.any():
            return None
        space = self.gm.table_space(self.ns, self.db, self.tb)
        rows = self.gm._rows_of(self.ns, self.db, self.tb, mirror, space)
        absent, _ = self.compiled.evaluate({p: _all_none_column(1) for p in self.compiled.paths})
        return np.where(rows >= 0, mask[np.maximum(rows, 0)], bool(absent[0]))

    def span(self, t0: float, built: bool, rows: int) -> None:
        """The statement's `graph_filter` span: the look-up (`hit`) or the
        making (`build`) of what the predicate contributes, and how many
        nodes pass it."""
        telemetry.stage(
            "graph_filter", t0, _time.perf_counter() - t0,
            outcome="build" if built else "hit", rows=rows,
        )


class GraphMirrors:
    """Per-datastore registry: (ns, db, src_tb, dir, ft) → PointerCsr, with a
    shared NodeInterner per (ns, db) so hops compose across tables."""

    def __init__(self):
        self._interners: Dict[Tuple[str, str], NodeInterner] = {}
        self._m: Dict[tuple, PointerCsr] = {}
        self._built: Set[Tuple[str, str, str]] = set()
        # dense composed operators + per-table compact id spaces
        self._spaces: Dict[tuple, dict] = {}  # (ns,db,tb) -> space dict
        self._dense: Dict[tuple, dict] = {}  # pair key -> operator dict
        self._csc: Dict[tuple, dict] = {}  # pair key -> composed sparse operator
        # (pair key, predicate binding) -> a filtered count's end weights on
        # the device, oldest first, held under a byte budget; and
        # (ns,db,tb) -> the column mirror's row of each compact id
        self._endw = ByteBudgetCache()
        self._mirror_rows: Dict[tuple, tuple] = {}
        # tables mid-build: deltas committed during the build scan are
        # buffered here and replayed after load (closes the scan→built gap)
        self._building: Dict[Tuple[str, str, str], List[tuple]] = {}
        self._build_locks: Dict[Tuple[str, str, str], threading.Lock] = {}
        self._lock = _locks.RLock("idx.graph.registry")
        # ingest-time prewarm (cnf.GRAPH_PREWARM): RELATE commits into a
        # not-yet-mirrored table arm a debounced timer; when ingest
        # quiesces, the mirror build + batched-count-kernel compiles run in
        # the background so the FIRST query doesn't pay the multi-second
        # (at scale, multi-minute) build + XLA-compile cliff
        self._ds = None  # weakref to the owning Datastore (set by bind_ds)
        self._prewarm_timers: Dict[Tuple[str, str, str], threading.Timer] = {}
        self._prewarm_deadline: Dict[Tuple[str, str, str], float] = {}
        self._prewarm_running: Set[Tuple[str, str, str]] = set()
        self._warmed_pairs: Set[tuple] = set()
        self._warmed_reach: Set[tuple] = set()  # set-kernel shapes warmed at every lane count
        # flight-recorder task ids of armed prewarms (bg.py lifecycle)
        self._task_ids: Dict[Tuple[str, str, str], int] = {}
        self._owner = None  # id(ds), for bg teardown scoping

    # ------------------------------------------------------------ plumbing
    def bind_ds(self, ds) -> None:
        """Bind the owning Datastore (weakly): prewarm builds open their own
        read transactions, which needs more than the commit-path hook has."""
        import weakref

        self._ds = weakref.ref(ds)
        self._owner = id(ds)

    def interner(self, ns: str, db: str) -> NodeInterner:
        with self._lock:
            it = self._interners.get((ns, db))
            if it is None:
                it = NodeInterner()
                self._interners[(ns, db)] = it
            return it

    def _get_or_create(self, ns, db, src_tb, d: bytes, ft: str) -> PointerCsr:
        k = (ns, db, src_tb, bytes(d), ft)
        with self._lock:
            m = self._m.get(k)
            if m is None:
                m = PointerCsr(self.interner(ns, db))
                self._m[k] = m
            return m

    def get(self, ns, db, src_tb, d: bytes, ft: str) -> Optional[PointerCsr]:
        return self._m.get((ns, db, src_tb, bytes(d), ft))

    def table_built(self, ns: str, db: str, src_tb: str) -> bool:
        return (ns, db, src_tb) in self._built

    def _forget_derived(self, stale) -> None:
        """Drop what was composed from mirrors that are going (caller holds
        _lock): id spaces, dense and sparse operators, end weights, warmed
        pairs whose key `stale` selects. Their generations count from a mirror's
        version and a table's size, and both start again with the new
        mirror and interner: an operator kept here would be served for
        the next graph of the same name."""
        for d in (self._spaces, self._dense, self._csc, self._mirror_rows):
            for k in [k for k in d if stale(k)]:
                del d[k]
        self._endw.forget(lambda k, _: stale(k[0]))
        self._warmed_pairs = {k for k in self._warmed_pairs if not stale(k)}

    def drop_table(self, ns: str, db: str, tb: str) -> None:
        """Forget a table's mirrors (REMOVE TABLE / bulk invalidation)."""
        with self._lock:
            self._built.discard((ns, db, tb))
            self._building.pop((ns, db, tb), None)
            for k in [k for k in self._m if k[:3] == (ns, db, tb)]:
                del self._m[k]
            self._forget_derived(lambda k: k[:2] == (ns, db) and tb in k[2:])

    def drop_db(self, ns: str, db: str) -> None:
        """Forget everything of one database (REMOVE DATABASE)."""
        with self._lock:
            self._built = {k for k in self._built if k[:2] != (ns, db)}
            self._building = {k: v for k, v in self._building.items() if k[:2] != (ns, db)}
            for k in [k for k in self._m if k[:2] == (ns, db)]:
                del self._m[k]
            self._interners.pop((ns, db), None)
            self._forget_derived(lambda k: k[:2] == (ns, db))

    def drop_ns(self, ns: str) -> None:
        """Forget everything of one namespace (REMOVE NAMESPACE)."""
        with self._lock:
            self._built = {k for k in self._built if k[0] != ns}
            self._building = {k: v for k, v in self._building.items() if k[0] != ns}
            for k in [k for k in self._m if k[0] == ns]:
                del self._m[k]
            for k in [k for k in self._interners if k[0] == ns]:
                del self._interners[k]
            self._forget_derived(lambda k: k[0] == ns)

    def clear(self) -> None:
        with self._lock:
            self._m.clear()
            self._built.clear()
            self._building.clear()
            self._interners.clear()
            self._forget_derived(lambda k: True)

    # ------------------------------------------------------------ build
    def ensure_table(self, ctx, src_tb: str) -> None:
        """Build every (dir, ft) mirror of `src_tb` with ONE scan over its
        `~` pointer keyspace. The scan runs on a FRESH snapshot opened after
        delta-buffering starts, so (a) deltas committed concurrently with
        the scan are buffered and replayed afterwards (apply is idempotent)
        and no committed edge can fall between the scan and the built flag,
        and (b) the querying transaction's own uncommitted writes never
        leak into the shared mirror (they force the exact KV walk anyway)."""
        ns, db = ctx.ns_db()
        self.build_table(ctx.ds(), ns, db, src_tb)

    def build_table(self, ds, ns: str, db: str, src_tb: str) -> None:
        """ensure_table's engine: also callable from the background prewarm
        thread, which has a Datastore but no request context."""
        key3 = (ns, db, src_tb)
        with self._lock:
            if key3 in self._built:
                return
            bl = self._build_locks.setdefault(key3, _locks.Lock("idx.graph.build"))
        with bl:
            with self._lock:
                if key3 in self._built:
                    return
                self._building[key3] = []
            t0 = _time.perf_counter()
            it = self.interner(ns, db)
            adjs: Dict[Tuple[bytes, str], Dict[int, List[int]]] = {}
            pre = keys.graph_prefix(ns, db, src_tb)
            txn = ds.transaction(False)
            try:
                for chunk in txn.batch(pre, prefix_end(pre), 4096):
                    for k, _ in chunk:
                        id_, d, ft, fk = keys.decode_graph(k, ns, db, src_tb)
                        if not isinstance(fk, Thing):
                            continue
                        s = it.intern(Thing(src_tb, id_))
                        t = it.intern(fk)
                        adjs.setdefault((bytes(d), ft), {}).setdefault(s, []).append(t)
            finally:
                txn.cancel()
            with self._lock:
                for (d, ft), adj in adjs.items():
                    self._get_or_create(ns, db, src_tb, d, ft).load(adj)
                pending = self._building.pop(key3, [])
                for delta in pending:
                    self._apply_one(delta)
                self._built.add(key3)
            telemetry.stage(
                "graph_scan", t0, _time.perf_counter() - t0,
                edges=sum(len(v) for adj in adjs.values() for v in adj.values()),
            )

    # ------------------------------------------------------------ deltas
    def _apply_one(self, delta: tuple) -> None:
        ns, db, src_tb, d, ft, src, dst, add = delta
        it = self.interner(ns, db)
        m = self._get_or_create(ns, db, src_tb, d, ft)
        m.apply(it.intern(src), it.intern(dst), add)

    def apply_deltas(self, deltas: Sequence[tuple]) -> None:
        """Apply committed edge-pointer deltas to built (or mid-build)
        tables. Each delta: (ns, db, src_tb, dir, ft, src, dst, add).
        Unbuilt tables ignore deltas — their eventual build scan sees the
        committed KV state anyway — but each such commit (re-)arms the
        debounced prewarm so the build + kernel compiles happen in the
        ingest→first-query gap instead of inside the first query.
        """
        unbuilt: Set[Tuple[str, str, str]] = set()
        for delta in deltas:
            key3 = tuple(delta[:3])
            with self._lock:
                if key3 in self._building:
                    self._building[key3].append(delta)
                    continue
                if key3 not in self._built:
                    unbuilt.add(key3)
                    continue
                self._apply_one(delta)
        if unbuilt:
            self._schedule_prewarm(unbuilt)

    # ------------------------------------------------------------ prewarm
    def _arm_timer(self, key3: Tuple[str, str, str], delay: float) -> None:
        """Start one self-identifying timer for key3 (caller holds _lock)."""
        from surrealdb_tpu import bg

        timer = bg.timer(
            delay, self._prewarm, key3, None,
            task_id=self._task_ids.get(key3),
            name=f"bg:graph_prewarm:{key3[2]}", start=False,
        )
        timer.args = (key3, timer)  # the callback must recognise itself
        self._prewarm_timers[key3] = timer
        timer.start()

    def _schedule_prewarm(self, keys3: Set[Tuple[str, str, str]]) -> None:
        """Debounce by DEADLINE, not by timer churn: each commit just moves
        the key's deadline forward; at most ONE live timer exists per key
        (it re-arms itself if it wakes early), so a million single-edge
        commits cost a million dict writes, not a million thread spawns."""
        import time as _time

        from surrealdb_tpu import cnf

        from surrealdb_tpu import bg

        if not cnf.GRAPH_PREWARM or self._ds is None:
            return
        delay = cnf.GRAPH_PREWARM_DELAY_SECS
        now = _time.monotonic()
        with self._lock:
            for key3 in keys3:
                self._prewarm_deadline[key3] = now + delay
                if key3 not in self._prewarm_timers:
                    # flight-recorder record: scheduled now, running when
                    # ingest quiesces and the build + kernel compiles start
                    self._task_ids[key3] = bg.register(
                        "graph_prewarm", target=".".join(key3), owner=self._owner
                    )
                    self._arm_timer(key3, delay)
                else:
                    tid = self._task_ids.get(key3)
                    if tid is not None:
                        bg.touch(tid)

    def _prewarm(self, key3: Tuple[str, str, str], timer) -> None:
        """Timer body (background thread): build the table's mirrors, then
        compile the batched count kernels its chains will hit. Best-effort —
        any failure leaves the lazy first-query path fully intact."""
        import time as _time

        from surrealdb_tpu import telemetry

        ns, db, tb = key3
        with self._lock:
            if self._prewarm_timers.get(key3) is not timer:
                return  # superseded — the newer timer owns this key
            remaining = self._prewarm_deadline.get(key3, 0.0) - _time.monotonic()
            if remaining > 0.001:
                # woke before the (commit-advanced) deadline: re-arm
                self._arm_timer(key3, remaining)
                return
            del self._prewarm_timers[key3]
            self._prewarm_deadline.pop(key3, None)
            self._prewarm_running.add(key3)
            task_id = self._task_ids.pop(key3, None)
        from surrealdb_tpu import bg

        if task_id is None:
            task_id = bg.register(
                "graph_prewarm", target=".".join(key3), owner=self._owner,
                trace_id=None,
            )
        try:
            with bg.run(task_id):
                ds = self._ds() if self._ds is not None else None
                if ds is None:
                    return
                telemetry.inc("graph_prewarm", stage="build")
                self.build_table(ds, ns, db, tb)
                self.warm_count_kernels(ns, db)
        except Exception:
            # the bg task record carries the error detail; the counter makes
            # a string of failed prewarms visible on /metrics
            telemetry.inc("prewarm_errors", subsystem="graph")
        finally:
            with self._lock:
                self._prewarm_running.discard(key3)

    def wait_prewarm(self, timeout: float = 30.0) -> bool:
        """Block until no prewarm timer or build is pending (test
        determinism helper, never used on the query path)."""
        import time as _time

        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            with self._lock:
                if not self._prewarm_timers and not self._prewarm_running:
                    return True
            _time.sleep(0.01)
        return False

    def shutdown(self, timeout: float = 10.0) -> None:
        """Teardown on Datastore.close(): cancel armed prewarm timers
        (resolving their flight-recorder records) and wait out in-flight
        builds, so no prewarm thread outlives its datastore."""
        from surrealdb_tpu import bg

        with self._lock:
            timers = list(self._prewarm_timers.values())
            self._prewarm_timers.clear()
            self._prewarm_deadline.clear()
            task_ids = list(self._task_ids.values())
            self._task_ids.clear()
        for t in timers:
            t.cancel()
        for tid in task_ids:
            bg.cancel(tid, "cancelled: datastore closed")
        self.wait_prewarm(timeout)

    def warm_count_kernels(self, ns: str, db: str) -> None:
        """Compile the batched count kernels for every composable
        `->edge->node` OUT-pair over built mirrors, at every lane count the
        serving runners can return (utils/num.py::count_lane_set: 8, 16,
        32, 64 at the dispatcher's width cap of 64) and the frontier pad
        they use (a sparse count of composed operators past its first pair:
        the operator's row pad, and a sweep less) — so a post-ingest burst
        of count-chain queries of any
        width starts on pre-compiled shapes (the r6 scale-1.0 log showed
        84.8s/26.4s first-query stalls that were exactly these compiles).
        Results are discarded; zero-weight lanes are harmless."""
        from surrealdb_tpu import cnf, telemetry

        if cnf.TPU_DISABLE:
            return
        import jax.numpy as jnp

        _kernels()
        dense_kernel = _JITTED["chain_count_batch_dense"]
        csc_kernel = _JITTED["chain_count_batch"]
        with self._lock:
            mkeys = [k for k in self._m if k[0] == ns and k[1] == db]
        pairs = [
            (tb, ft, ft2)
            for (_, _, tb, d, ft) in mkeys
            if d == keys.DIR_OUT
            for (_, _, tb2, d2, ft2) in mkeys
            if tb2 == ft and d2 == keys.DIR_OUT
        ]
        fsz = _next_pow2(max(1, cnf.TPU_GRAPH_FRONTIER_PAD))
        # exactly the lane counts the serving runners can return
        lane_set = count_lane_set()
        for tb, et, dt_ in pairs:
            pkey = (ns, db, tb, et, dt_)
            with self._lock:
                if pkey in self._warmed_pairs:
                    continue
                self._warmed_pairs.add(pkey)
            spec1 = ([tb], [keys.DIR_OUT], [et])
            spec2 = ([et], [keys.DIR_OUT], [dt_])
            # chains self-compose only when the pair loops back to its
            # source table (person->knows->person); otherwise warm 1 pair
            max_pairs = 3 if dt_ == tb else 1
            telemetry.inc("graph_prewarm", stage="kernels")
            try:
                op = self._dense_pair(ns, db, spec1, spec2)
            except Exception:
                op = None
            if op is not None:
                from surrealdb_tpu import compile_log

                n0 = op["ns_pad"]
                no_end = jnp.zeros(n0, dtype=jnp.int32)
                for lanes in lane_set:
                    frs = jnp.asarray(np.full((lanes, fsz), n0, dtype=np.int32))
                    cws = jnp.asarray(np.zeros((lanes, fsz), dtype=np.int32))
                    # the bare count's program, and the one that ends in a
                    # rider's weights (a predicate on the final node part)
                    for outdeg, ends in ((op["outdeg"], None), (None, (no_end,) * lanes)):
                        for c in range(1, max_pairs + 1):
                            try:
                                As = (op["A"],) * (c - 1)
                                with compile_log.tracked(
                                    "graph_dense",
                                    _dense_shape_key(lanes, fsz, n0, As, ends is not None),
                                    prewarmed=True,
                                ):
                                    dense_kernel(As, outdeg, frs, cws, n0=n0, end_weights=ends)
                            except Exception:
                                telemetry.inc(
                                    "prewarm_errors", subsystem="graph_count"
                                )
                continue
            # dense doesn't fit (oversized tables / fat multiplicities):
            # warm the CSC prefix-sum form the serving path will use instead,
            # over the operand it will sweep: the pair's composed operator,
            # or the two record-level mirrors where that is refused
            try:
                from surrealdb_tpu import compile_log

                cop = self._csc_pair(ns, db, spec1, spec2)
                if cop is not None:
                    n_cap, last_hop = cop["n_pad"], ((cop["indptr"],),)
                    hop = ((cop["cptr"], cop["csrc"]),)
                    # both entries of _csc_chain_count: from the seeds (one
                    # pair; more where the seeds' rows pass the pad), and
                    # from their rows of the first operator, at its pad and
                    # a sweep less
                    swept = [(fsz, c - 1) for c in range(1, max_pairs + 1)]
                    if cop["row_pad"]:
                        swept += [(cop["row_pad"], c - 2) for c in range(2, max_pairs + 1)]
                    chains = [(pad, (hop,) * n) for pad, n in dict.fromkeys(swept)]
                else:
                    m1 = self._hop_mirrors(ns, db, spec1)
                    m2 = self._hop_mirrors(ns, db, spec2)
                    if len(m1) != 1 or len(m2) != 1:
                        continue
                    n_cap = _next_pow2(len(self.interner(ns, db)))
                    csc1, csc2 = m1[0].device_csc(), m2[0].device_csc()
                    last_hop = ((m2[0].device_arrays()[0],),)
                    # `->et->tb` repeated c times = 2c specs; the final
                    # spec is a degree reduction (no CSC)
                    chains = [
                        (fsz, tuple(
                            ((csc1,) if i % 2 == 0 else (csc2,))
                            for i in range(2 * c - 1)
                        ))
                        for c in range(1, max_pairs + 1)
                    ]
                # end weights ride the composed operand alone
                no_end = None if cop is None else jnp.zeros(n_cap, dtype=jnp.int32)
                for lanes in lane_set:
                    endings = [(last_hop, None)] + ([((), (no_end,) * lanes)] if cop is not None else [])
                    for last, ends in endings:
                        for pad, csc_hops in chains:
                            frs = jnp.asarray(np.full((lanes, pad), n_cap, dtype=np.int32))
                            cws = jnp.asarray(np.zeros((lanes, pad), dtype=np.int32))
                            with compile_log.tracked(
                                "graph_csc",
                                _csc_shape_key(lanes, pad, n_cap, csc_hops, last, ends is not None),
                                prewarmed=True,
                            ):
                                csc_kernel(csc_hops, last, frs, cws, n_cap=n_cap, end_weights=ends)
            except Exception:
                telemetry.inc("prewarm_errors", subsystem="graph_count")

    # ------------------------------------------------------------ traversal
    def _hop_mirrors(self, ns, db, spec) -> List[PointerCsr]:
        srcs, dirs, fts = spec
        out = []
        for tb in srcs:
            for d in dirs:
                for ft in fts:
                    m = self.get(ns, db, tb, d, ft)
                    if m is not None and m.adj:
                        out.append(m)
        return out

    def _host_hop(self, ns, db, frontier: np.ndarray, counts: np.ndarray, spec):
        out: Dict[int, int] = {}
        for m in self._hop_mirrors(ns, db, spec):
            with m._lock:  # deltas may mutate adj lists concurrently
                for i, c in zip(frontier.tolist(), counts.tolist()):
                    for dst in m.adj.get(int(i), ()):
                        out[dst] = out.get(dst, 0) + c
        nodes = np.fromiter(sorted(out), dtype=np.int32, count=len(out))
        return nodes, np.array([out[int(n)] for n in nodes], dtype=np.int32)

    def _chain_work_estimate(self, ns, db, specs, counts) -> float:
        """Expected edges traversed by a count chain: Σ over hops of the
        frontier size estimate × that hop's average degree (random-graph
        expectation from mirror edge counts). Decides device routing — a
        1-seed chain over a degree-4 graph is ~40 edges of HOST work no
        matter how many total edges the graph has, while the same seed on
        a degree-100 social graph explodes past any host budget."""
        frontier_est = float(counts.sum())
        work = 0.0
        for sp in specs:
            deg = 0.0
            for m in self._hop_mirrors(ns, db, sp):
                deg += m.edge_count / max(len(m.adj), 1)
            frontier_est *= deg
            work += frontier_est
            if work >= 1e12:
                break
        return work

    # ------------------------------------------------ dense composed counts
    def table_space(self, ns: str, db: str, tb: str) -> dict:
        """Compact per-table id space over the shared interner: sorted
        global ids of `tb`'s nodes + a global->local inverse array.
        Incrementally extended as the interner grows (append-only)."""
        it = self.interner(ns, db)
        with self._lock:
            sp = self._spaces.get((ns, db, tb))
            if sp is None:
                sp = self._spaces[(ns, db, tb)] = {
                    "globals": [], "inv": {}, "scanned": 0,
                }
            n = len(it.node_of)
            if sp["scanned"] < n:
                g, inv = sp["globals"], sp["inv"]
                for i in range(sp["scanned"], n):
                    if it.node_of[i].tb == tb:
                        inv[i] = len(g)
                        g.append(i)
                sp["scanned"] = n
            return sp

    @staticmethod
    def _pad128(n: int) -> int:
        return max(((n + 127) // 128) * 128, 128)

    def _pair_parts(self, ns, db, spec1, spec2):
        """What composing one `->edge->node` spec pair starts from: the
        operator's cache key and generation, the two mirrors and the two
        tables' compact id spaces. None for a pair no single operator
        spans (a hop over several tables or directions, an empty table)."""
        srcs1, dirs1, fts1 = spec1
        srcs2, dirs2, fts2 = spec2
        if len(srcs1) != 1 or len(fts1) != 1 or len(dirs1) != 1:
            return None
        if len(fts2) != 1 or len(dirs2) != 1:
            return None
        src_tb, edge_tb, dst_tb = srcs1[0], fts1[0], fts2[0]
        m1s = self._hop_mirrors(ns, db, spec1)
        m2s = self._hop_mirrors(ns, db, spec2)
        if len(m1s) != 1 or len(m2s) != 1:
            return None
        m1, m2 = m1s[0], m2s[0]
        sp_s = self.table_space(ns, db, src_tb)
        sp_d = self.table_space(ns, db, dst_tb)
        n_s, n_d = len(sp_s["globals"]), len(sp_d["globals"])
        if not n_s or not n_d:
            return None
        key = (ns, db, src_tb, dirs1[0], edge_tb, dirs2[0], dst_tb)
        # the versions are read before either adjacency: an operator composed
        # from a later state under this generation is recomposed by the next
        # count, never served stale
        return key, (m1.version, m2.version, n_s, n_d), m1, m2, sp_s, sp_d

    def _dense_pair(self, ns, db, spec1, spec2):
        """Composed dense operator for one `->edge->node` spec pair:
        A[local_src, local_dst] = number of 2-hop paths through the edge
        table, bf16 on device. None if anything about the pair doesn't fit
        the dense form: multi-table hops, a node table over
        TPU_GRAPH_DENSE_MAX, or an operator past the limb limit of
        chain_count_batch_dense (a multiplicity of 256 or more, which bf16
        would round, or 255 x the largest column sum reaching 2**24, the
        float32 accumulator's exact range)."""
        import jax.numpy as jnp
        from surrealdb_tpu import cnf

        parts = self._pair_parts(ns, db, spec1, spec2)
        if parts is None:
            return None
        key, gen, m1, m2, sp_s, sp_d = parts
        n_s, n_d = gen[2:]
        if max(n_s, n_d) > cnf.TPU_GRAPH_DENSE_MAX:
            return None
        with self._lock:
            op = self._dense.get(key)
        if op is not None and op["gen"] == gen:
            return op if op["fits"] else None
        # host composition: one pass over m1's edges, mapping each middle
        # edge-record to its m2 destinations
        t0 = _time.perf_counter()
        inv_s, inv_d = sp_s["inv"], sp_d["inv"]
        ns_pad, nd_pad = self._pad128(n_s), self._pad128(n_d)
        # copy both adjacencies up front: the O(paths) composition loop must
        # not hold mirror locks (it would stall every concurrent RELATE)
        with m1._lock:
            adj1 = {k: list(v) for k, v in m1.adj.items()}
        with m2._lock:
            adj2 = {k: list(v) for k, v in m2.adj.items()}
        rows_s, rows_d = [], []
        for g_src, mids in adj1.items():
            ls = inv_s.get(g_src)
            if ls is None:
                continue
            for mid in mids:
                for g_dst in adj2.get(mid, ()):
                    ld = inv_d.get(g_dst)
                    if ld is not None:
                        rows_s.append(ls)
                        rows_d.append(ld)
        rows_s = np.asarray(rows_s, np.int64)
        rows_d = np.asarray(rows_d, np.int64)
        cells, mult = np.unique(rows_s * nd_pad + rows_d, return_counts=True)
        colsum = np.bincount(rows_d, minlength=nd_pad)
        op = {"gen": gen, "fits": False, "key": key}
        if mult.max(initial=0) < 256 and 255 * int(colsum.max()) < 1 << 24:
            import ml_dtypes

            A = np.zeros(ns_pad * nd_pad, dtype=ml_dtypes.bfloat16)
            A[cells] = mult.astype(ml_dtypes.bfloat16)
            op.update(
                fits=True,
                n_src=n_s,
                n_dst=n_d,
                ns_pad=ns_pad,
                nd_pad=nd_pad,
                A=jnp.asarray(A.reshape(ns_pad, nd_pad)),
                outdeg=jnp.asarray(
                    np.bincount(rows_s, minlength=ns_pad).astype(np.int32)
                ),
                space_src=sp_s,
                paths=(rows_s, rows_d),
            )
        # a refusal is remembered too: an operator past the limb limit is
        # not recomposed by every statement of its generation
        with self._lock:
            self._dense[key] = op
        if not op["fits"]:
            return None
        telemetry.stage(
            "graph_dense_compose", t0, _time.perf_counter() - t0, bytes=op["A"].nbytes
        )
        return op

    def _csc_pair(self, ns, db, spec1, spec2):
        """Composed sparse operator for one `->edge->node` spec pair: the
        2-hop paths src -> edge record -> dst as a node->node CSC in the two
        tables' compact ids, shaped as PointerCsr.device_csc() shapes a
        mirror's (dst-sorted `csrc` padded by _csc_arrays with the
        sentinel `n_pad`, bin bounds `cptr`, and the source-side `indptr`
        whose differences are the out-degrees of a count's last pair), so
        chain_count_batch sweeps one hop a pair over [lanes, n_pad + 1]
        where the record-level mirrors cost two hops over the shared id
        space (persons AND edge records: 2,097,152 slots for 24,328 persons
        at SNB SF3). A repeated path stays a repeated entry: int32 sums wrap
        the same in any order. Beside them, on the host, the operator by
        source (`by_src`: that `indptr` and the destinations in
        _compose_coo's source order, so a node's row is one slice) and
        `row_pad`, the power of two at or above its longest row (_row_pad:
        0 where a hub's row passes ROW_PAD_MAX): what a
        count that leaves from its seeds reads its first hop from
        (_csc_chain_count). Rows and swept arrays are made together, one
        generation's, so an acknowledged RELATE makes both anew. Cached a
        generation like the dense operator,
        the refusal too. None when no single operator spans the pair, or
        when the operator would hold more entries than the two mirrors it
        composes (a hop through a NODE table multiplies in-degrees by
        out-degrees; through an edge table every record has one far end)."""
        import jax.numpy as jnp

        parts = self._pair_parts(ns, db, spec1, spec2)
        if parts is None:
            return None
        key, gen, m1, m2, sp_s, sp_d = parts
        with self._lock:
            op = self._csc.get(key)
        if op is not None and op["gen"] == gen:
            return op if op["fits"] else None
        # neither mirror's lock is held while composing: host_arrays() hands
        # out one compaction's arrays, which later deltas leave as they are
        ip1, ix1 = m1.host_arrays()
        ip2, ix2 = m2.host_arrays()
        t0 = _time.perf_counter()
        coo = _compose_coo(
            ip1, ix1, ip2, ix2, sp_s, sp_d, max_paths=int(ip1[-1]) + int(ip2[-1])
        )
        op = {"gen": gen, "fits": coo is not None, "key": key}
        if coo is not None:
            ls, ld = coo
            n_pad = _next_pow2(max(gen[2:]))
            cptr, csrc = _csc_arrays(ls, ld, n_pad)
            indptr = np.zeros(n_pad + 1, dtype=np.int32)
            np.cumsum(np.bincount(ls, minlength=n_pad), out=indptr[1:])
            nbytes = cptr.nbytes + csrc.nbytes + indptr.nbytes
            t1 = _time.perf_counter()
            telemetry.stage("graph_csc_build", t0, t1 - t0, bytes=nbytes, paths=ls.size, slots=csrc.size)
            op.update(
                n_pad=n_pad,
                src_tb=key[2],
                dst_tb=key[6],
                cptr=jnp.asarray(cptr),
                csrc=jnp.asarray(csrc),
                indptr=jnp.asarray(indptr),
                space_src=sp_s,
                by_dst=(cptr, csrc),
                by_src=(indptr, ld.astype(np.int32)),
                row_pad=_row_pad(int(np.diff(indptr).max())),
            )
            # asynchronous, as device_csc()'s: the host's hand-off
            telemetry.stage(
                "graph_csc_upload", t1, _time.perf_counter() - t1, bytes=nbytes
            )
        with self._lock:
            self._csc[key] = op
        return op if op["fits"] else None

    # ------------------------------------------------ a predicate on the end
    def _rows_of(self, ns, db, tb, mirror, space) -> np.ndarray:
        """The column mirror's row of each compact id of `tb` (-1: a node
        no record stands behind, the far end of a dangling edge), kept
        until the mirror is rebuilt or the table's space grows."""
        n = len(space["globals"])
        with self._lock:
            got = self._mirror_rows.get((ns, db, tb))
        if got is not None and got[0] is mirror and got[1] == n:
            return got[2]
        row_of, node_of = mirror.id_index(), self.interner(ns, db).node_of
        rows = np.fromiter(
            (row_of.get(repr(node_of[g].id), -1) for g in space["globals"][:n]),
            dtype=np.int64, count=n,
        )
        with self._lock:
            self._mirror_rows[(ns, db, tb)] = (mirror, n, rows)
        return rows

    def _end_weights(self, end: "_EndFilter", op: dict, size: int):
        """A filtered count's end weights over the last pair `op`, on the
        device, and whether this statement made them: one array a
        (pair, predicate text, bound values), good while the operator's
        generation and the node table's column mirror are the ones it was
        made from, so an acknowledged RELATE or UPDATE is seen by the next
        count. None where the column mirror cannot answer for this reader."""
        import jax.numpy as jnp

        mirror = end.mirror()
        if mirror is None:
            return None
        # the pair's key first (what _forget_derived selects by), then the
        # weights' length: a pair's dense and sparse operators pad apart
        key = (op["key"] + (size,), end.compiled.binding_key())
        with self._lock:
            got = self._endw.get(key)
            if got is not None and got["gen"] == op["gen"] and got["mirror"] is mirror:
                return got["w"], got["rows"], False
        mask = end.local_mask()
        if mask is None:
            return None
        t0 = _time.perf_counter()
        by_dst = op.get("by_dst")
        if by_dst is None:  # a dense operator sorts its paths on first need
            by_dst = op["by_dst"] = _csc_arrays(*op["paths"], op["nd_pad"])
        cptr, csrc = by_dst
        passing = np.flatnonzero(mask[: len(cptr) - 1])
        w = _weights_into(cptr, csrc, passing, size)
        t1 = _time.perf_counter()
        telemetry.stage("graph_filter_build", t0, t1 - t0, bytes=w.nbytes)
        got = {"gen": op["gen"], "mirror": mirror, "w": jnp.asarray(w), "rows": int(mask.sum())}
        telemetry.stage("graph_filter_upload", t1, _time.perf_counter() - t1, bytes=w.nbytes)
        with self._lock:
            self._endw.put(key, got, w.nbytes)
        return got["w"], got["rows"], True

    def _chain_pairs(self, ns, db, specs, pair_of):
        """One composed operator a `->edge->node` pair of the chain, by
        `pair_of` (_dense_pair or _csc_pair); None for an odd spec count or
        a pair `pair_of` refuses."""
        if len(specs) < 2 or len(specs) % 2 != 0:
            return None
        ops = []
        for i in range(0, len(specs), 2):
            op = pair_of(ns, db, specs[i], specs[i + 1])
            if op is None:
                return None
            ops.append(op)
        return ops

    def _csc_ops(self, ns, db, specs):
        """The chain's composed sparse operators, one a pair, where one
        kernel can sweep them in a row: each pair's destination table the
        next pair's source table, all padded to one node space. None
        otherwise (_chain_pairs)."""
        ops = self._chain_pairs(ns, db, specs, self._csc_pair)
        if ops is not None and any(
            a["dst_tb"] != b["src_tb"] or a["n_pad"] != b["n_pad"]
            for a, b in zip(ops, ops[1:])
        ):
            return None
        return ops

    def _filtered(self, end: "_EndFilter", op: dict, size: int):
        """The end weights of a count's last pair `op` under `end`, with the
        statement's `graph_filter` span; None where the predicate cannot
        ride (the KV walk)."""
        t0 = _time.perf_counter()
        got = self._end_weights(end, op, size)
        if got is None:
            return None
        w, rows, built = got
        end.span(t0, built, rows)
        return w

    def _dense_chain_count(self, ns, db, frontier, counts, specs, dispatch, t_enter=None, end=None):
        """Count chain as composed dense matmuls (chain_count_batch_dense),
        exact under 2**31 at any degree. Returns None when the chain doesn't
        fit the dense form (odd spec count, or a pair _dense_pair refuses) —
        _device_chain then uses the CSC form. With `end`, the predicate on
        the chain's final node part, the count ends in the rider's end
        weights where the bare count ends in the last pair's out-degrees:
        riders of any bound values share a dispatch (the weights ride as
        payload; the key says only that the batch is weighted). `_UNFUSED`
        where the predicate cannot ride."""
        import jax.numpy as jnp
        from surrealdb_tpu import cnf

        ops = self._chain_pairs(ns, db, specs, self._dense_pair)
        if ops is None:
            return None
        # chain spaces must line up: pair i's dst space is pair i+1's src
        for a, b in zip(ops, ops[1:]):
            if a["nd_pad"] != b["ns_pad"] or a["n_dst"] != b["n_src"]:
                return None
        _kernels()
        kernel = _JITTED["chain_count_batch_dense"]
        n0 = ops[0]["ns_pad"]
        fsz = _next_pow2(max(frontier.size, cnf.TPU_GRAPH_FRONTIER_PAD))
        fr, cw, seeded = _local_seeds(
            ops[0]["space_src"]["inv"], frontier, counts, fsz, n0
        )
        how = "none" if end is None else "fused"
        if not seeded:
            _served("dense", t_enter, filter=how)
            return 0
        endw = None
        if end is not None:
            endw = self._filtered(end, ops[-1], ops[-1]["ns_pad"])
            if endw is None:
                return _UNFUSED
        As = tuple(op["A"] for op in ops[:-1])
        weighted = end is not None
        outdeg = None if weighted else ops[-1]["outdeg"]
        key = (
            "gdense", fsz, n0,
            tuple(id(a) for a in As),
            ("w", ops[-1]["ns_pad"]) if weighted else id(outdeg),
        )

        def runner(payloads):
            from surrealdb_tpu import compile_log

            frs, cws = _stack_lanes(payloads, fsz, n0)
            ends = _lane_end_weights(payloads, len(frs)) if weighted else None
            with compile_log.tracked(
                "graph_dense", _dense_shape_key(len(frs), fsz, n0, As, weighted)
            ):
                out = kernel(
                    As, outdeg, jnp.asarray(frs), jnp.asarray(cws), n0=n0,
                    end_weights=ends,
                )
            return _collect_counts(out, len(payloads), len(frs))

        _served("dense", t_enter, filter=how)
        # with an operator product the program runs its 8 lanes in the same
        # 0.54 ms at any width (snbsf1.hop3_c8: the operator's 194 MB read),
        # far shorter than its riders' way back through the host, and a
        # launch costs the host the same whatever it carries: one deep and
        # gathering, as the sparse count from the rows is (dbs/dispatch.py::
        # SWEEP_DEPTH, _gather). On the chip, eight sessions, one deep
        # WITHOUT the gathering: 2.68 riders a launch against 1.91, 486.8
        # stmt/s at p50 16.54 against 429-449 at 17.5 (PERF.md section 7 w,
        # PR 42); as shipped: section 6, PR 45. A one-pair count (a dot
        # product, no cell) keeps the queue's own depth
        paced = bool(As)
        return dispatch.submit(
            key, (fr, cw, endw) if weighted else (fr, cw), runner,
            depth=SWEEP_DEPTH if paced else None, gather=paced,
        )

    def _csc_chain_count(self, ns, db, frontier, counts, specs, dispatch, t_enter=None, end=None):
        """Count chain in the scatter-free CSC prefix-sum form
        (chain_count_batch): what no dense operator can hold. A chain of
        composable `->edge->node` pairs whose tables line up sweeps their
        composed node->node operators (_csc_pair), one hop a pair in the
        node table's compact ids; any other chain (a hop over several
        tables, an odd spec count, pairs padded to different spaces) sweeps
        the record-level mirrors, one hop a spec in the shared id space.
        One kernel either way, chosen from what the chain is. A predicate
        on the final node part (`end`) rides the composed operand alone, as
        _dense_chain_count's does: the end weights live in the node
        table's compact ids.

        Over composed operators the first pair is not swept for where the
        seeds' rows of it fit its `row_pad`: a sweep from a seed is a pass
        over every slot of the operator for one adjacency row, which the
        operator's source-sorted rows hold as a slice (_seed_rows). The
        kernel then takes the frontier after one hop and sweeps the pairs
        between the first and the last (none for a chain of two pairs: a
        degree or end-weight reduction over the compact frontier). The
        frontier pads to `row_pad`, which the operator fixes, so every rider
        of a generation shares one bucket and one program whatever its
        seed's degree. Seeds whose rows together pass the pad (a FROM of
        many records), and every seed of an operator whose longest row is
        too long to pad to (_row_pad), are swept from, as every
        records-operand count is."""
        from surrealdb_tpu import cnf

        fsz = _next_pow2(max(frontier.size, cnf.TPU_GRAPH_FRONTIER_PAD))
        ops = self._csc_ops(ns, db, specs)
        weighted = end is not None
        endw, how, first_hop = None, "fused" if weighted else "none", "sweep"
        if ops is not None:
            operand, n_cap = "composed", ops[0]["n_pad"]
            fr, cw, seeded = _local_seeds(
                ops[0]["space_src"]["inv"], frontier, counts, fsz, n_cap
            )
            if not seeded:
                _served("csc", t_enter, operand, filter=how)
                return 0
            if weighted:
                endw = self._filtered(end, ops[-1], n_cap)
                if endw is None:
                    return _UNFUSED
            swept = ops[:-1]
            rows = _seed_rows(ops[0], fr[:seeded], cw[:seeded]) if swept and ops[0]["row_pad"] else None
            if rows is not None:
                (fr, cw), fsz, swept, first_hop = rows, ops[0]["row_pad"], swept[1:], "rows"
            csc_hops = tuple(((op["cptr"], op["csrc"]),) for op in swept)
            last_hop = () if weighted else ((ops[-1]["indptr"],),)
        elif weighted:
            return _UNFUSED
        else:
            operand = "records"
            hop_mirrors = [self._hop_mirrors(ns, db, sp) for sp in specs]
            if not all(hop_mirrors):
                _served("csc", t_enter, operand)
                return 0
            n_cap = _next_pow2(len(self.interner(ns, db)))
            fr = np.full(fsz, n_cap, dtype=np.int32)
            fr[: frontier.size] = frontier
            cw = np.zeros(fsz, dtype=np.int32)
            cw[: counts.size] = counts
            csc_hops = tuple(
                tuple(m.device_csc() for m in mirrors) for mirrors in hop_mirrors[:-1]
            )
            last_hop = tuple((m.device_arrays()[0],) for m in hop_mirrors[-1])
        _kernels()
        batch_kernel = _JITTED["chain_count_batch"]
        # the frontier's pad, what is swept and the operands' ids: only
        # chains over the same arrays coalesce (a weighted batch's riders
        # bring their own ends, whatever they bound)
        key = (
            "gchain", fsz, n_cap, len(csc_hops),
            tuple(id(a) for hop in csc_hops for pair in hop for a in pair),
            "w" if weighted else tuple(id(p) for (p,) in last_hop),
        )

        def runner(payloads):
            from surrealdb_tpu import compile_log

            frs, cws = _stack_lanes(payloads, fsz, n_cap)
            ends = _lane_end_weights(payloads, len(frs)) if weighted else None
            with compile_log.tracked(
                "graph_csc",
                _csc_shape_key(len(frs), fsz, n_cap, csc_hops, last_hop, weighted),
            ):
                # the NumPy lanes as they are: the jitted call puts them on
                # the device itself, where two jnp.asarray in front of it
                # gave the interpreter lock up and took it back twice more
                # (a launch phase 4.1 -> 2.7 ms under eight sessions)
                out = batch_kernel(csc_hops, last_hop, frs, cws, n_cap=n_cap, end_weights=ends)
            return _collect_counts(out, len(payloads), len(frs), sweeps=len(csc_hops))

        _served("csc", t_enter, operand, filter=how, first_hop=first_hop)
        # from the rows, one sweep left: a kernel shorter than its riders'
        # way back through the host, so the bucket is one deep and gathers
        # (dbs/dispatch.py::SWEEP_DEPTH, _gather; PERF.md section 6, PR 42).
        # Every count that keeps the sweep from its seeds keeps the queue's
        # own depth, as does one with two sweeps left (the device clocks it:
        # a second batch in flight keeps it fed) or none (a small program)
        paced = first_hop == "rows" and len(csc_hops) == 1
        return dispatch.submit(
            key, (fr, cw, endw) if weighted else (fr, cw), runner,
            depth=SWEEP_DEPTH if paced else None, gather=paced,
        )

    def _device_chain(
        self, ns, db, frontier: np.ndarray, counts: np.ndarray, specs,
        count_only: bool = False, dispatch=None, t_enter=None, end=None,
    ):
        """Run the remaining hops entirely on device in ONE dispatch. The
        one place a device count is produced: with a dispatcher a count
        coalesces with its concurrent peers (dbs/dispatch.py
        leader-follower) as composed dense matmuls on the MXU, or in the
        CSC prefix-sum form where the chain doesn't fit a dense operator.
        Otherwise the fused chain kernel: one upload, H weighted gathers
        with on-device scatter-add dedup between hops, one download at the
        end. Every static dimension (frontier size, max degree, node
        capacity, dedup output) is pow2-rounded so steady writes don't
        recompile."""
        if count_only and dispatch is not None:
            res = self._dense_chain_count(
                ns, db, frontier, counts, specs, dispatch, t_enter=t_enter, end=end
            )
            if res is None:
                res = self._csc_chain_count(
                    ns, db, frontier, counts, specs, dispatch, t_enter=t_enter, end=end
                )
            return res
        import jax.numpy as jnp

        from surrealdb_tpu import cnf, compile_log

        chain_kernel = _kernels()
        it = self.interner(ns, db)
        n_cap = _next_pow2(len(it))
        # floor the frontier pad: XLA compiles per static shape, and chains
        # arriving with 90- vs 130-node frontiers must share ONE compiled
        # kernel to coalesce
        fsz = _next_pow2(max(frontier.size, cnf.TPU_GRAPH_FRONTIER_PAD))
        fr = np.full(fsz, n_cap, dtype=np.int32)
        fr[: frontier.size] = frontier
        cw = np.zeros(fsz, dtype=np.int32)
        cw[: counts.size] = counts

        if count_only:
            _served("csc", t_enter, "records")
        hops, mds, out_sizes = [], [], []
        width = fsz
        for spec in specs:
            mirrors = self._hop_mirrors(ns, db, spec)
            if not mirrors:
                if count_only:
                    return 0
                e = np.empty(0, dtype=np.int32)
                return e, e
            hop_arrs, hop_mds, total = [], [], 0
            for m in mirrors:
                hop_arrs.append(m.device_arrays())
                md = _next_pow2(max(m.max_degree, 1))
                hop_mds.append(md)
                total += width * md
            hops.append(tuple(hop_arrs))
            mds.append(tuple(hop_mds))
            width = _next_pow2(min(total, n_cap))
            out_sizes.append(width)
        hops, mds, out_sizes = tuple(hops), tuple(mds), tuple(out_sizes)
        with compile_log.tracked(
            "graph_chain", (fsz, n_cap, mds, out_sizes, bool(count_only))
        ):
            out = chain_kernel(
                hops, jnp.asarray(fr), jnp.asarray(cw),
                mds=mds, n_cap=n_cap, out_sizes=out_sizes,
                count_only=count_only,
            )
        if count_only:
            return int(out)
        u = np.asarray(out[0])
        c = np.asarray(out[1])
        keep = c > 0
        return u[keep].astype(np.int32), c[keep].astype(np.int32)

    def _chain_specs(self, ctx, tables: Set[str], parts: List) -> list:
        """The hop specs (source tables, directions, foreign tables) of a
        chain that leaves from `tables`, each source table's mirrors built:
        a hop filtered on foreign-table ft lands entirely in table ft, so
        the next hop's sources are exactly p.what."""
        dir_map = {"out": [keys.DIR_OUT], "in": [keys.DIR_IN], "both": [keys.DIR_IN, keys.DIR_OUT]}
        specs = []
        for p in parts:
            for tb in tables:
                self.ensure_table(ctx, tb)
            specs.append((sorted(tables), dir_map[p.dir], p.what))
            tables = set(p.what)
        return specs

    def _chain_frontier(self, ctx, start: List[Thing], parts: List, count_only: bool = False, where=None):
        """Shared frontier machinery for chain()/chain_count(): returns
        (frontier int32[], counts int32[], interner) — or the scalar path
        count when count_only (the device chain then downloads one int).
        `where` is the compiled predicate of a count chain's final node
        part: the count takes the bare chain's route with it (from the
        seed to the device, or hop by hop on the host where the chain is
        small), or is None where the predicate cannot ride."""
        from surrealdb_tpu import cnf

        t_enter = _time.perf_counter()
        ns, db = ctx.ns_db()
        it = self.interner(ns, db)
        specs = self._chain_specs(ctx, {t.tb for t in start}, parts)
        cmap: Dict[int, int] = {}
        for t in start:
            i = it.lookup(t)
            if i is not None:
                cmap[i] = cmap.get(i, 0) + 1
        frontier = np.fromiter(sorted(cmap), dtype=np.int32, count=len(cmap))
        counts = np.array([cmap[int(i)] for i in frontier], dtype=np.int32)
        dispatch = getattr(ctx.ds(), "dispatch", None)
        end = None
        if where is not None:
            end = _EndFilter(self, ctx, parts[-1].what[0], where)
            if end.mirror() is None:
                return None
        if (
            count_only
            and not cnf.TPU_DISABLE
            and dispatch is not None
            and frontier.size
            and self._chain_work_estimate(ns, db, specs, counts)
            >= cnf.TPU_GRAPH_COUNT_EDGES
        ):
            # big count chain: to the device in one tiny-upload batched
            # dispatch, with no hop walked over the live `adj` dicts
            # (_host_hop: Python an edge under the mirror's lock, which
            # serializes concurrent clients, and frontiers whose padded size
            # differs a rider, so they would not coalesce). The dense form
            # and the record-level sweep leave from the seed itself; the
            # sweep of composed operators leaves from the seeds' rows of the
            # first operator, a whole-array slice of one generation's arrays
            # padded to a size the operator fixes (_csc_chain_count): every
            # rider still shares one compiled shape
            res = self._device_chain(
                ns, db, frontier, counts, specs,
                count_only=True, dispatch=dispatch, t_enter=t_enter, end=end,
            )
            return None if res is _UNFUSED else res
        i = 0
        while i < len(specs):
            # a hop goes on device once the CURRENT frontier is device-sized,
            # or — for count-only chains — as soon as the NEXT frontier would
            # be: the whole remaining chain fuses into one dispatch either
            # way, and skipping the host hops keeps every concurrent query's
            # shapes identical so they coalesce into one vmapped launch
            md = max(
                (m.max_degree for m in self._hop_mirrors(ns, db, specs[i])),
                default=0,
            )
            device_now = frontier.size >= cnf.TPU_GRAPH_ONDEVICE_THRESHOLD or (
                count_only
                and frontier.size * md >= cnf.TPU_GRAPH_ONDEVICE_THRESHOLD
            )
            # a filtered count that did not leave from the seed is small
            # by the estimate: it stays on the host, where the mask is
            # applied to the last frontier (a mid-chain hand-over would
            # sweep record-level operands, which carry no end weights)
            if not cnf.TPU_DISABLE and device_now and end is None:
                res = self._device_chain(
                    ns, db, frontier, counts, specs[i:],
                    count_only=count_only, dispatch=dispatch, t_enter=t_enter,
                )
                if count_only:
                    return res
                frontier, counts = res
                break
            frontier, counts = self._host_hop(ns, db, frontier, counts, specs[i])
            i += 1
        if count_only:
            if end is not None:
                t0 = _time.perf_counter()
                mask = end.local_mask()
                if mask is None:
                    return None
                inv = self.table_space(ns, db, end.tb)["inv"]
                local = [inv.get(int(g), -1) for g in frontier.tolist()]
                counts = counts[[0 <= j < mask.size and bool(mask[j]) for j in local]]
                end.span(t0, True, int(mask.sum()))
            _served("host", t_enter, filter="none" if end is None else "fused")
            return int(counts.sum())
        return frontier, counts, it

    def chain(
        self,
        ctx,
        start: List[Thing],
        parts: List,  # List[PGraph]
    ) -> List[Thing]:
        """Run a maximal chain of cond-free graph parts `->a->b->c` as
        batched frontier hops: host adjacency while the frontier is small,
        then the rest of the chain on device once it crosses
        TPU_GRAPH_ONDEVICE_THRESHOLD.

        Multiplicity matches the reference's flatten-without-dedup semantics
        (sql/value/get.rs:404-446): the frontier is deduplicated between hops
        but each node carries its path count, and the final result expands
        each node count times. Result order is deterministic (ascending
        intern order ≈ build-scan key order, with delta-added nodes after)
        but not identical to the KV walk's key order; graph hop ordering is
        unspecified upstream.
        """
        frontier, counts, it = self._chain_frontier(ctx, start, parts)
        out: List[Thing] = []
        for j, c in zip(frontier, counts):
            out.extend([it.node_of[int(j)]] * int(c))
        return out

    def chain_count(self, ctx, start: List[Thing], parts: List, where=None) -> Optional[int]:
        """Path count of a chain WITHOUT materializing the expanded result —
        `count(->a->b->c)` sums the frontier's path counts directly (on a
        3-hop over 1M edges the Python expansion would dominate the whole
        query; the device already holds the counts, and the fused chain
        kernel downloads a single scalar). `where`: the compiled predicate
        (ops/predicates.py) of `->(c WHERE ...)`, the final part, which
        then names one node table; the count is of the paths that end at a
        node passing it, or None where the predicate cannot ride the
        mirrors (the caller walks the KV and says so: count_walked)."""
        return self._chain_frontier(ctx, start, parts, count_only=True, where=where)

    @staticmethod
    def count_walked(t_enter: float) -> None:
        """A count chain with a WHERE that the KV walk served, from
        `t_enter` to now: `form=host`, `filter=host`."""
        _served("host", t_enter, filter="host")


    # ------------------------------------------------ the set a chain reaches
    def chain_distinct(
        self, ctx, start: Thing, parts: List, where=None, deepest: Optional[List] = None,
        keep: Optional[Tuple[dict, Any]] = None,
    ) -> Optional[List[Thing]]:
        """`array::distinct(<chain>)` from the record `start`: the nodes a
        walk of the chain's parts ends at, each once, in ascending order of
        their ids in the mirrors (a table's compact ids rise with the
        interner's), without the multiset chain() lays out. `where`: the
        compiled predicate of the final part, as chain_count takes it; the
        set is then of the nodes that pass. None where the predicate cannot
        ride the mirrors (the caller walks the KV and says so:
        reach_walked).

        `deepest`: the parts of the longest chain among the statement's
        `array::distinct` expressions that this chain is a prefix of, under
        the same predicate as bound now (sql/path.py::graph_chain_distinct,
        which finds both out without a second predicate), and `keep` the
        statement's ring memo with the family's key in it. This runs
        `deepest` ONCE and keeps there the set after each of its hops (ring
        h: a walk of exactly h pairs) with what a span says of the run; the
        statement's other expressions never come here: they read their
        ring off the memo (ring), before any of the preparation below. So
        `array::concat` of the 1-, 2- and 3-pair chains from one record is
        one device dispatch, one compiled predicate and one look-up of the
        operators, not three.

        Where the chain's `->edge->node` pairs have composed sparse
        operators that line up (_csc_ops), the rings come from the device
        for every start whose row fits the operator's row pad: no work
        estimate and no threshold, so a statement's dispatches are fixed by
        its text. Ring 1 is the first operator's row of the start, read on
        the host; the later rings are chain_reach_batch's, one sweep a
        pair (of a chain of two pairs whose operators bound every walk by a
        pad: the frontier's rows of the second, _walk_pad), with the
        rider's predicate as a bit mask kept on the device a binding
        (_reach_mask).
        A chain of one pair makes no dispatch. Anything else (TPU_DISABLE,
        a part over several tables, an odd number of parts, a pair no
        operator spans, a start whose row of the first operator passes the
        pad rows are read at: ROW_PAD_MAX): the host's hop-by-hop walk over
        the mirrors with every frontier a set (_host_reach)."""
        from surrealdb_tpu import cnf

        t_enter = _time.perf_counter()
        ns, db = ctx.ns_db()
        deepest = parts if deepest is None else deepest
        specs = self._chain_specs(ctx, {start.tb}, deepest)
        end = None
        if where is not None:
            end = _EndFilter(self, ctx, parts[-1].what[0], where)
            if end.mirror() is None:
                return None
        dispatch = getattr(ctx.ds(), "dispatch", None)
        ops = None if cnf.TPU_DISABLE or dispatch is None else self._csc_ops(ns, db, specs)
        got = (
            self._device_reach(ns, db, start, specs, ops, end, dispatch) if ops is not None
            else self._host_reach(ns, db, start, specs, end)
        )
        if got is None:
            return None
        got["filter"] = "none" if end is None else "fused"
        if keep is not None:
            memo, key = keep
            memo[key] = got
        ctx.executor.op_end = _time.perf_counter()  # the `materialise` span starts here
        things = self.ring(ctx, got, parts, t_enter, fill=True)
        if things is None and deepest is not parts:
            # a ring the deepest chain's program does not keep for this chain
            return self.chain_distinct(ctx, start, parts, where=where)
        return things

    def ring(self, ctx, got: dict, parts: List, t_enter: float, fill: bool = False) -> Optional[List[Thing]]:
        """The records of the ring that `got`, one run of chain_distinct as
        the statement's memo keeps it, holds for the chain `parts` (a
        prefix of the chain that ran, under its predicate as bound now:
        the memo's key says so), with the expression's `graph_prepare`
        span and `graph_reach` count; `fill`: for the expression that ran
        it. None where the run kept no ring for this chain."""
        ring = got["rings"].get(len(parts) - 1)
        if ring is None:
            return None
        t_ready = (got["t_ready"] if fill else None) or _time.perf_counter()
        ns, db = ctx.ns_db()
        things = self._things(ns, db, ring)
        _reached(
            got["form"], t_enter, t_ready, got["filter"], got["operand"],
            depth=len(parts) // 2, memo="fill" if fill else "hit", ids=len(things), rings=len(got["rings"]),
            last_hop=got.get("last_hop") if fill else None,
        )
        return things

    def _device_reach(self, ns, db, start: Thing, specs, ops: List[dict], end, dispatch) -> Optional[dict]:
        """chain_distinct's rings from the composed operators `ops`: ring h
        (a chain part's index 2h - 1) as (table, ascending compact ids),
        kept for the hops that land in the last pair's table (the mask is
        over that table, and no chain of the statement reads another).
        None where the predicate's mask cannot be made for this reader."""
        row = self._start_row(ns, db, start, ops[0])
        if not row.size:  # a record no edge leaves reaches nothing
            return self._no_rings(ops)
        if len(ops) > 1 and row.size > ops[0]["row_pad"]:
            # a hub's row passes the pad its operator reads rows at
            # (ROW_PAD_MAX; 0: the operator has such a row): the host's walk
            return self._host_reach(ns, db, start, specs, end)
        plan = self._reach_plan(ops, end)
        if plan is None:
            return None
        got = self._first_ring(plan, row)
        if plan["swept"]:
            got["t_ready"] = _time.perf_counter()  # stamped at the dispatch submit
            out = dispatch.submit(plan["key"], self._rider(plan, row), plan["runner"], depth=SWEEP_DEPTH, gather=True)
            self._swept_rings(plan, got, out)
        return got

    def reach_group(
        self, ctx, starts: List[Thing], parts: List, where=None, families: int = 1,
    ) -> Optional[List[Optional[dict]]]:
        """chain_distinct's run of the chain `parts` for MANY start records
        of one table, as one group: the rows of a SELECT whose field list
        asks every row for `array::distinct(<chain>)` (sql/path.py::
        fill_reach_groups, which keeps each start's run in the statement's
        ring memo, where the rows' expressions then find their rings).
        The operators are looked up, the predicate's mask made and the
        runner built ONCE; each start whose row of the first operator fits
        the pad brings that row as its frontier, and all of them go to the
        dispatch queue in one call (DispatchQueue.submit_many: a rider a
        start, `submitted` up by their number), so that with the bucket
        idle a statement's rows ride one launch and, with other sessions
        at work, share it with theirs. A start no edge leaves has empty
        rings, a hub's row past the pad takes the host's walk, as
        chain_distinct serves them; a start the walk cannot serve either
        has None. None for the whole group where the chain is not one the
        composed operators serve (TPU_DISABLE, no dispatcher, a pair no
        operator spans, a predicate whose mask cannot be made for this
        reader): every row then evaluates as it does alone. With the
        statement's `graph_reach_group` span and counter (_grouped);
        `families`: how many chains the statement's fill serves this way,
        for the span to say."""
        from surrealdb_tpu import cnf

        t_enter = _time.perf_counter()
        ns, db = ctx.ns_db()
        dispatch = getattr(ctx.ds(), "dispatch", None)
        if cnf.TPU_DISABLE or dispatch is None or not starts:
            return None
        specs = self._chain_specs(ctx, {starts[0].tb}, parts)
        ops = self._csc_ops(ns, db, specs)
        if ops is None:
            return None
        end = None
        if where is not None:
            end = _EndFilter(self, ctx, parts[-1].what[0], where)
            if end.mirror() is None:
                return None
        rows = [self._start_row(ns, db, start, ops[0]) for start in starts]
        pad = ops[0]["row_pad"] if len(ops) > 1 else None  # one pair: the row is the answer, nothing is swept
        riding = [i for i, row in enumerate(rows) if row.size and (pad is None or row.size <= pad)]
        plan = self._reach_plan(ops, end) if riding else None
        if riding and plan is None:
            return None
        gots: List[Optional[dict]] = [None] * len(starts)
        for i in riding:
            gots[i] = self._first_ring(plan, rows[i])
        for i, (start, row) in enumerate(zip(starts, rows)):
            if gots[i] is None:
                gots[i] = self._host_reach(ns, db, start, specs, end) if row.size else self._no_rings(ops)
        rode: List[int] = []
        t_ready = _time.perf_counter()
        if plan is not None and plan["swept"]:
            outs = dispatch.submit_many(
                plan["key"], [self._rider(plan, rows[i]) for i in riding], plan["runner"],
                depth=SWEEP_DEPTH, gather=True, rode=rode,
            )
            for i, out in zip(riding, outs):
                gots[i]["t_ready"] = t_ready
                self._swept_rings(plan, gots[i], out)
        for got in gots:
            if got is not None:
                got["filter"] = "none" if end is None else "fused"
        ctx.executor.op_end = _time.perf_counter()  # the `materialise` span starts here
        _grouped(
            t_enter, t_ready, rows=len(starts), riders=len(rode), families=families, launches=len(set(rode)),
            filter="none" if end is None else "fused", depth=len(parts) // 2,
            last_hop=plan["last_hop"] if rode else None,
        )
        return gots

    def _start_row(self, ns, db, start: Thing, op: dict) -> np.ndarray:
        """The record `start`'s row of the composed operator `op`, a
        destination once a path: the first hop of a set chain from it.
        Empty for a record that is no source of the operator."""
        g = self.interner(ns, db).lookup(start)
        seed = None if g is None else op["space_src"]["inv"].get(g)
        indptr, dst = op["by_src"]
        return dst[:0] if seed is None else dst[indptr[seed] : indptr[seed + 1]]

    @staticmethod
    def _no_rings(ops: List[dict]) -> dict:
        """_device_reach's answer for a record no edge leaves."""
        tb = ops[-1]["dst_tb"]
        return {"form": "csc", "operand": "composed", "t_ready": None,
                "rings": {2 * h + 1: (tb, np.empty(0, dtype=np.int64)) for h in range(len(ops))}}

    def _reach_plan(self, ops: List[dict], end) -> Optional[dict]:
        """What every rider of the set chain over `ops` under the predicate
        `end` shares: the node space, the mask (_reach_mask: on the device
        and as the host applies it to a first hop), and for the pairs after
        the first the kernel's operands, the dispatch key and the runner,
        and where the last hop comes from (`last_hop`: `rows` where ONE
        pair follows the first and the two operators bound every walk by a
        pad, _walk_pad: one bucket and one program whatever the rider;
        else `sweep`). Made once a statement's chain, for one start
        (_device_reach) or for a group of them (reach_group). None where
        the mask cannot be made for this reader."""
        n_cap, tb = ops[0]["n_pad"], ops[-1]["dst_tb"]
        masked = self._reach_mask(end, ops[-1], n_cap)
        if masked is None:
            return None
        words, mask = masked
        swept, fsz = ops[1:], ops[0]["row_pad"]
        plan = {"ops": ops, "tb": tb, "n_cap": n_cap, "fsz": fsz, "words": words, "mask": mask, "swept": swept}
        if not swept:
            return plan
        _kernels()
        # ONE hop after the first, every walk of the two operators inside
        # the pad: the kernel reads the frontier's rows of the second
        # operator (its `indptr`, and its destinations in source order,
        # uploaded for this) where it would sweep every slot of it
        walk_pad = _walk_pad(ops[0], ops[1]) if len(swept) == 1 else 0
        kernel = _JITTED["chain_reach_batch"]
        if walk_pad:
            csc_hops, slots = (((swept[0]["indptr"], self._rows_on_device(swept[0])),),), walk_pad
        else:
            csc_hops = tuple(((op["cptr"], op["csrc"]),) for op in swept)
            slots = sum(int(op["csrc"].shape[0]) for op in swept)
        plan["last_hop"] = "rows" if walk_pad else "sweep"
        # the frontier's pad, the walk's (0: swept) and the operands' ids:
        # riders of any start and any bound value share a batch (the mask
        # rides as payload)
        plan["key"] = ("greach", fsz, walk_pad, n_cap, tuple(id(a) for hop in csc_hops for pair in hop for a in pair))

        def runner(payloads):
            from surrealdb_tpu import compile_log

            lanes = count_lanes(len(payloads))
            frs = np.full((lanes, fsz), n_cap, dtype=np.int32)
            for i, p in enumerate(payloads):
                frs[i] = p[0]
            with compile_log.tracked("graph_reach", _reach_shape_key(lanes, fsz, n_cap, csc_hops, walk_pad)):
                out = kernel(csc_hops, frs, _lane_end_weights(payloads, lanes, at=1), n_cap=n_cap, walk_pad=walk_pad)
            return _collect_rings(out, len(payloads), lanes, slots)

        plan["runner"] = runner
        self._warm_reach(csc_hops, fsz, n_cap, walk_pad)
        return plan

    def _rows_on_device(self, op: dict):
        """The composed operator `op`'s destinations in source order
        (`by_src`) on the device, padded as its swept arrays are
        (path_slots, the sentinel `n_pad` past the last row: an edge more
        keeps the program's shape): what chain_reach_batch gathers its walks
        from. Uploaded once a generation, with the first set chain that
        reads rows, and never for an operator whose walks pass the pad."""
        import jax.numpy as jnp

        rows = op.get("dst_rows")
        if rows is None:
            t0 = _time.perf_counter()
            dst = op["by_src"][1]
            padded = np.full(path_slots(dst.size), op["n_pad"], dtype=np.int32)
            padded[: dst.size] = dst
            rows = jnp.asarray(padded)
            telemetry.stage("graph_csc_upload", t0, _time.perf_counter() - t0, bytes=padded.nbytes)
            # two first statements at once: one array wins, so that every
            # rider's dispatch key names the same operand
            with self._lock:
                rows = op.setdefault("dst_rows", rows)
        return rows

    @staticmethod
    def _first_ring(plan: dict, row: np.ndarray) -> dict:
        """A rider's answer before the sweep: the first operator's row IS
        the first hop, masked on the host."""
        got = {"form": "csc", "operand": "composed", "rings": {}, "t_ready": None}
        if plan["ops"][0]["dst_tb"] == plan["tb"]:
            first, mask = np.unique(row), plan["mask"]
            got["rings"][1] = (plan["tb"], first if mask is None else first[mask[first]])
        return got

    @staticmethod
    def _rider(plan: dict, row: np.ndarray) -> tuple:
        """A rider's payload: its first hop as the sweep's frontier, padded
        at the node space's sentinel, and the mask's words on the device.
        The bucket is one batch deep and gathers, as the count's with one
        sweep left (_csc_chain_count), at TWO sweeps left too: a set
        statement's way back through the host is long (hundreds of record
        ids to fetch from), so at the queue's own depth its batches stay
        under 2 wide (1,728 dispatches in 14 s, 215 stmt/s, p50 36.3 ms;
        one deep and gathering 3.8 wide, 837, 223-229, 34.3-34.5: PERF.md
        section 6, PR 44)."""
        fr = np.full(plan["fsz"], plan["n_cap"], dtype=np.int32)
        fr[: row.size] = row
        return fr, plan["words"]

    @staticmethod
    def _swept_rings(plan: dict, got: dict, out) -> None:
        """The swept hops' rings of one rider (chain_reach_batch's words, a
        row a hop; its walk where the one hop was read from the rows) into
        its answer, for the hops that land in the mask's table."""
        got["last_hop"] = plan["last_hop"]
        for h, (op, ring) in enumerate(zip(plan["swept"], out), start=1):
            if op["dst_tb"] == plan["tb"]:
                got["rings"][2 * h + 1] = (plan["tb"], _ring_ids(ring))

    def _warm_reach(self, csc_hops, fsz: int, n_cap: int, walk_pad: int = 0) -> None:
        """Compile chain_reach_batch (the program a plan launches: the sweep
        of `csc_hops`, or at a `walk_pad` the read of the one hop's rows) at
        every lane count the runner can return (count_lane_set) for the
        shapes a statement is being served at, once a shape and in the
        background (cnf.GRAPH_PREWARM), as idx/ivf.py warms a search's
        other tiles: the first burst wider than this statement's batch then
        starts on compiled programs. Not with warm_count_kernels' shapes: a
        deployment that never asks for a set composes and compiles nothing
        for one."""
        from surrealdb_tpu import bg, cnf

        shape = _reach_shape_key(0, fsz, n_cap, csc_hops, walk_pad)
        with self._lock:
            if not cnf.GRAPH_PREWARM or shape in self._warmed_reach:
                return
            self._warmed_reach.add(shape)
        kernel = _JITTED["chain_reach_batch"]

        def warm():
            from surrealdb_tpu import compile_log

            words = np.zeros(-(-n_cap // 32), dtype=np.uint32)
            for lanes in count_lane_set():
                try:
                    with compile_log.tracked(
                        "graph_reach", _reach_shape_key(lanes, fsz, n_cap, csc_hops, walk_pad), prewarmed=True
                    ):
                        kernel(
                            csc_hops, np.full((lanes, fsz), n_cap, dtype=np.int32), (words,) * lanes,
                            n_cap=n_cap, walk_pad=walk_pad,
                        )
                except Exception:
                    telemetry.inc("prewarm_errors", subsystem="graph_reach")

        # under the arming statement's context, as idx/ft_mirror.py's warms:
        # a wait behind the statement's own compile lands in that trace
        bg.spawn(
            "shape_warm", f"graph_reach:f{fsz}:n{n_cap}:h{len(csc_hops)}" + (f":w{walk_pad}" if walk_pad else ""),
            contextvars.copy_context().run, warm, owner=self._owner,
        )

    def _reach_mask(self, end: Optional["_EndFilter"], op: dict, n_cap: int):
        """The rider's predicate over the last pair `op`'s destination
        table as chain_reach_batch reads it (_pack_mask, on the device) and
        as the host applies it to a first hop's row (bool [n_cap], None:
        every node passes), kept a (pair, predicate binding) while the
        operator's generation and the table's column mirror are the ones it
        was made from, as _end_weights keeps a count's end weights: an
        acknowledged UPDATE is seen by the next statement. With the
        statement's `graph_filter` span. None where the column mirror
        cannot answer for this reader."""
        import jax.numpy as jnp

        t0 = _time.perf_counter()
        mirror = None if end is None else end.mirror()
        key = (op["key"] + ("mask", n_cap), None if end is None else end.compiled.binding_key())
        with self._lock:
            got = self._endw.get(key)
        built = got is None or got["gen"] != op["gen"] or got["mirror"] is not mirror
        if built:
            mask = None
            if end is not None:
                passing = end.local_mask()
                if passing is None:
                    return None
                mask = np.zeros(n_cap, dtype=bool)
                mask[: min(passing.size, n_cap)] = passing[:n_cap]
            words = _pack_mask(np.ones(n_cap, dtype=bool) if mask is None else mask, n_cap)
            t1 = _time.perf_counter()
            telemetry.stage("graph_filter_build", t0, t1 - t0, bytes=words.nbytes)
            got = {"gen": op["gen"], "mirror": mirror, "w": jnp.asarray(words), "mask": mask,
                   "rows": n_cap if mask is None else int(mask.sum())}
            telemetry.stage("graph_filter_upload", t1, _time.perf_counter() - t1, bytes=words.nbytes)
            with self._lock:
                self._endw.put(key, got, words.nbytes + n_cap)
        if end is not None:
            end.span(t0, built, got["rows"])
        return got["w"], got["mask"]

    def _host_reach(self, ns, db, start: Thing, specs, end) -> Optional[dict]:
        """chain_distinct's rings by the host's walk over the mirrors, every
        frontier a set (_host_hop with the counts held at 1): the set after
        every part. Without a predicate the rings are in the interner's
        ids, whatever tables a part names; with one, they are kept for the
        parts that land in the predicate's table, in its compact ids, and
        masked. None where the mask cannot be made for this reader."""
        t0 = _time.perf_counter()
        mask = inv = None
        if end is not None:
            mask = end.local_mask()
            if mask is None:
                return None
            inv = self.table_space(ns, db, end.tb)["inv"]
        g = self.interner(ns, db).lookup(start)
        frontier = np.array([] if g is None else [g], dtype=np.int32)
        rings = {}
        for j, spec in enumerate(specs):
            frontier, _ = self._host_hop(ns, db, frontier, np.ones(frontier.size, dtype=np.int32), spec)
            if end is None:
                rings[j] = (None, frontier)
            elif list(spec[2]) == [end.tb]:
                local = np.array([inv.get(int(g), -1) for g in frontier.tolist()], dtype=np.int64)
                local = local[(local >= 0) & (local < mask.size)]
                rings[j] = (end.tb, local[mask[local]])
        if end is not None:
            end.span(t0, True, int(mask.sum()))
        return {"form": "host", "operand": None, "rings": rings, "t_ready": None}

    def _things(self, ns, db, ring) -> List[Thing]:
        """The records of one ring. Compact ids of a table are one take
        from the table's records as an object array, kept with the table's
        id space and extended as it grows; ids of the interner (a host
        walk without a predicate) are looked up one by one."""
        tb, ids = ring
        node_of = self.interner(ns, db).node_of
        if tb is None:
            return [node_of[g] for g in ids.tolist()]
        sp = self.table_space(ns, db, tb)
        with self._lock:
            arr, globals_ = sp.get("things"), sp["globals"]
            have = 0 if arr is None else arr.size
            if arr is None or have < len(globals_):
                grown = np.empty(len(globals_), dtype=object)
                grown[:have] = arr
                for i in range(have, len(globals_)):
                    grown[i] = node_of[globals_[i]]
                arr = sp["things"] = grown
        return arr[ids].tolist()

    @staticmethod
    def reach_walked(t_enter: float, depth: int, ids: int) -> None:
        """An `array::distinct(<chain>)` with a WHERE that the KV walk
        served, from `t_enter` to now: `form=host`, `filter=host`."""
        _reached("host", t_enter, _time.perf_counter(), filter="host", depth=depth, ids=ids)


def graftcheck_sites():
    """Audit contracts of the four graph count/set/expand kernels
    (compile_log subsystems `graph_dense` / `graph_csc` / `graph_reach` /
    `graph_chain`): representative 2-hop chains over pow2-padded
    adjacencies at the dispatch lane widths the batched paths serve."""
    import jax
    import jax.numpy as jnp

    n0, n_cap, fsz, E = 256, 256, 64, 1024

    def build_dense(shape):
        import ml_dtypes

        _kernels()
        kernel = _JITTED["chain_count_batch_dense"]
        lanes = shape["lanes"]
        As = tuple(
            jax.ShapeDtypeStruct((n0, n0), jnp.dtype(ml_dtypes.bfloat16))
            for _ in range(shape["hops"])
        )
        end = jax.ShapeDtypeStruct((n0,), jnp.int32)
        args = (
            As,
            (end,) * lanes if shape.get("weighted") else end,
            jax.ShapeDtypeStruct((lanes, fsz), jnp.int32),
            jax.ShapeDtypeStruct((lanes, fsz), jnp.int32),
        )
        if shape.get("weighted"):
            return (lambda A, ws, fr, cw: kernel(A, None, fr, cw, n0=n0, end_weights=ws)), args
        return (lambda A, od, fr, cw: kernel(A, od, fr, cw, n0=n0)), args

    def build_csc(shape):
        _kernels()
        kernel = _JITTED["chain_count_batch"]
        lanes = shape["lanes"]
        csc_hops = tuple(
            ((jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32),
              jax.ShapeDtypeStruct((path_slots(shape.get("paths", E)),), jnp.int32)),)
            for _ in range(shape["hops"] - 1)
        )
        lanes_fsz = jax.ShapeDtypeStruct((lanes, fsz), jnp.int32)
        if shape.get("weighted"):
            ends = (jax.ShapeDtypeStruct((n_cap,), jnp.int32),) * lanes
            return (
                lambda ch, ws, fr, cw: kernel(ch, (), fr, cw, n_cap=n_cap, end_weights=ws),
                (csc_hops, ends, lanes_fsz, lanes_fsz),
            )
        last_hop = ((jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32),),)
        return (
            lambda ch, lh, fr, cw: kernel(ch, lh, fr, cw, n_cap=n_cap),
            (csc_hops, last_hop, lanes_fsz, lanes_fsz),
        )

    def build_reach(shape):
        _kernels()
        kernel = _JITTED["chain_reach_batch"]
        lanes = shape["lanes"]
        # a swept hop's (cptr, csrc) and, at a walk pad, the one hop's
        # operator by source (its row bounds, its destinations padded as
        # the swept arrays are) have the same shapes
        csc_hops = tuple(
            ((jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32),
              jax.ShapeDtypeStruct((path_slots(E),), jnp.int32)),)
            for _ in range(shape["hops"])
        )
        masks = (jax.ShapeDtypeStruct((n_cap // 32,), jnp.uint32),) * lanes
        walk_pad = shape.get("walk_pad", 0)
        return (
            lambda ch, fr, ms: kernel(ch, fr, ms, n_cap=n_cap, walk_pad=walk_pad),
            (csc_hops, jax.ShapeDtypeStruct((lanes, fsz), jnp.int32), masks),
        )

    def build_chain(shape):
        kernel = _kernels()
        hops = tuple(
            ((jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32),
              jax.ShapeDtypeStruct((E,), jnp.int32)),)
            for _ in range(shape["hops"])
        )
        mds = tuple((8,) for _ in range(shape["hops"]))
        out_sizes = tuple(n_cap for _ in range(shape["hops"]))
        count_only = shape["count_only"]
        args = (
            hops,
            jax.ShapeDtypeStruct((fsz,), jnp.int32),
            jax.ShapeDtypeStruct((fsz,), jnp.int32),
        )
        return (
            lambda h, fr, cw: kernel(
                h, fr, cw, mds=mds, n_cap=n_cap, out_sizes=out_sizes,
                count_only=count_only,
            ),
            args,
        )

    # as served: every lane count the runners can return (count_lane_set),
    # ending in the last pair's degrees or (`w`) in a rider's own weights
    lane_shapes = [
        {"label": f"l{lanes}_f{fsz}_n{n0}_h2" + ("_w" if weighted else ""),
         "lanes": lanes, "hops": 2, "weighted": weighted}
        for weighted in (False, True)
        for lanes in count_lane_set()
    ]
    return [
        {
            "subsystem": "graph_dense",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("int32",),
            # four limbs a lane, one operator a pair before the last
            "shapes": lane_shapes,
            "build": build_dense,
        },
        {
            "subsystem": "graph_csc",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("int32",),
            # and one operand whose slot count is no power of two: the
            # path array pads to path_slots (1,025 paths: 1,152 slots)
            "shapes": lane_shapes + [
                {"label": f"l8_f{fsz}_n{n0}_h2_p{E + 1}", "lanes": 8, "hops": 2, "weighted": False, "paths": E + 1}
            ],
            "build": build_csc,
        },
        {
            "subsystem": "graph_reach",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("uint32", "int32"),
            # the rings after two swept hops (a chain of three pairs from
            # the first operator's rows), bit-packed, at every lane count;
            # and the one hop of a chain of two pairs read from the rows,
            # a destination a slot of the walk pad (int32)
            "shapes": [
                {"label": f"l{lanes}_f{fsz}_n{n_cap}_h2", "lanes": lanes, "hops": 2}
                for lanes in count_lane_set()
            ] + [
                {"label": f"l{lanes}_f{fsz}_n{n_cap}_h1_w512", "lanes": lanes, "hops": 1, "walk_pad": 512}
                for lanes in count_lane_set()
            ],
            "build": build_reach,
        },
        {
            "subsystem": "graph_chain",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("int32",),
            "shapes": [
                {"label": "f64_n256_h2_expand", "hops": 2, "count_only": False},
                {"label": "f64_n256_h3_count", "hops": 3, "count_only": True},
            ],
            "build": build_chain,
        },
    ]
