"""Whole-pipeline columnar SELECT lowering over the column mirror.

PR 4 vectorized the WHERE; everything after the mask (ORDER BY, GROUP BY
aggregates, projections, START/LIMIT) still ran row-at-a-time through
`dbs/iterator.py`'s postprocessing loop. This module lowers the REST of the
pipeline onto the same typed column arrays (idx/column_mirror.py), the
MonetDB/X100 operator-at-a-vector model applied to PAPER.md layer 7's
Iterator/group.rs contract:

- **ORDER BY + START/LIMIT** become mask -> stable multi-key argsort over
  mirror columns (np.lexsort over (ordinal, nan-rank, within-type) key
  planes reproducing `apply_order`'s value_cmp total order exactly — NONE
  ordinal 0, cross-type by ordinal, NaN below every number, string/datetime
  dense ranks); rows whose order cells are OTHER-tagged (arrays, objects,
  records...) fall back to a per-row sort_key computed from the decoded
  value, merged through the identical stable-sort algorithm.
- **GROUP BY + aggregates** become factorize (vectorized np.unique codes
  when every key cell is scalar, dict-of-first-appearance otherwise — the
  two agree because python `==` and the float plane collapse 1/1.0/true
  identically) + segment-reduce (np.bincount / minimum.at / maximum.at)
  reproducing `aggregate_groups` byte-for-byte: int sums stay int (exact
  past-2^53 guard re-folds in python), min/max return the FIRST minimal
  member's value (int vs float tag preserved), NaN folds match python's
  order-dependent min/max, empty aggregates yield NONE. `math::sum` and
  `math::mean` also take an integer expression of columns and constants
  (`+`, `-`, `*`: `math::sum(price * (100 - discount))`), evaluated a row
  in int64 under interval bounds proved from the columns' cells.
- **What runs where** (since PR 40). A grouped statement whose WHERE is a
  conjunction of comparisons with constants, whose keys are low-cardinality
  columns and whose aggregates are counts and integer sums runs ON THE
  DEVICE: the columns it names stand in HBM as int32 planes
  (idx/column_mirror.py `device_columns`) and the statement is one rider of
  one dispatch of ops/column_agg.py::grouped_aggregate, which sweeps the
  planes once for every rider of the launch and returns exact limb sums.
  A summed expression is expanded here into a polynomial of its columns:
  the launch sums the monomials and the rider folds its own coefficients
  in, so no constant of a statement is in a program or a dispatch key, and
  the key names the columns a launch reads, not their places among them.
  `grouped_route` is the one rule; its outcome is on the statement's
  `column_prepare` span and in `column_pipeline{outcome}`. What of the
  rule a statement's constants cannot move is kept with the mirror build
  (`placements`), and a sweep's dispatch bucket is one deep: statements
  that arrive while a sweep is in flight ride the next one together. Every other
  grouped statement, and every ordered one, runs on the HOST in NumPy over
  the mirror's arrays; what does not lower at all keeps the row path. The
  three return identical rows.
- **Late materialization**: only the row ids surviving sort + START/LIMIT
  are decoded; plain-field projections are reconstructed straight off the
  columns (`id` from the row-id map) — a `SELECT VALUE id ... ORDER BY ...
  LIMIT k` touches ZERO documents. Any row whose projected cells include an
  OTHER tag decodes its document once and runs the ordinary row-path
  projection for exactness.
- **Cost hook**: `choose_strategy` picks row vs columnar per statement from
  mirror presence/staleness, table size, and pipeline shape; the decision +
  inputs land in plan notes so EXPLAIN ANALYZE shows why a path was taken.
- **Cluster partials**: `partial_aggregate` computes per-shard partial
  aggregates (count / exact int sums / min-max with NaN + int-float-tie
  exactness flags / mean as sum+count / first-member values keyed by the
  encoded record key) under a first-live-replica ownership mask, and
  `merge_partials` folds them on the coordinator — shards that cannot
  prove byte-exact mergeability (float sums, NaN folds, cross-shard
  int/float ties) flag it and the statement falls back to the full
  gather-and-replay scatter. Refuse, never answer wrong.

Every shape that cannot lower declines with a reason counted in the
`column_pipeline{outcome}` counter and keeps the (always-correct) row path.
"""

from __future__ import annotations

import time as _time
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from surrealdb_tpu import cnf
from surrealdb_tpu.dbs.dispatch import SWEEP_DEPTH
from surrealdb_tpu.ops.predicates import (
    F64_EXACT_INT,
    ORD_OF_TAG,
    TAG_BOOL,
    TAG_DATETIME,
    TAG_FLOAT,
    TAG_INT,
    TAG_NONE,
    TAG_NULL,
    TAG_OTHER,
    TAG_STR,
    CompiledPredicate,
    _const_value,
    _depth_limit,
    _is_const,
    compile_where,
)
from surrealdb_tpu.sql.ast import BinaryOp, FunctionCall
from surrealdb_tpu.sql.path import Idiom, PField, get_path
from surrealdb_tpu.sql.value import (
    NONE,
    Datetime,
    Null,
    Thing,
    sort_key,
    truthy,
)

# the aggregate calls this module can segment-reduce; everything else in the
# iterator's _AGGREGATES set declines (the row path handles it)
LOWERED_AGGREGATES = {
    "count": "count",
    "math::sum": "sum",
    "math::min": "min",
    "math::max": "max",
    "math::mean": "mean",
}

_F64_EXACT = float(1 << 53)
_UNRESOLVED = object()  # sentinel: order key provably not a source column
_MISSING = object()


def _outcome(reason: str) -> None:
    from surrealdb_tpu import telemetry

    telemetry.inc("column_pipeline", outcome=reason)


# ------------------------------------------------------------------ specs
class OrderSpec:
    """One resolved ORDER BY key: the SOURCE column path it reads (``id``
    reads the row-id map) plus the original idiom's part names — needed in
    VALUE mode, where `apply_order` digs the idiom into dict-valued rows."""

    __slots__ = ("path", "asc", "parts")

    def __init__(self, path: str, asc: bool, parts: Optional[List[str]] = None):
        self.path = path
        self.asc = asc
        self.parts = parts


class AggSpec:
    """One aggregate: `kind` count|count_arg|sum|min|max|mean over a plain
    `path`, or (sum and mean) over `expr`, the AST of an integer expression
    of columns and constants (`+`, `-`, `*`)."""

    __slots__ = ("kind", "path", "expr")

    def __init__(self, kind: str, path: Optional[str], expr=None):
        self.kind = kind
        self.path = path
        self.expr = expr


class GroupedField:
    """One projected field of a grouped SELECT: either a lowered aggregate
    or a plain path evaluated on the group's first member."""

    __slots__ = ("field", "agg", "path")

    def __init__(self, field, agg: Optional[AggSpec], path: Optional[str]):
        self.field = field
        self.agg = agg
        self.path = path


class GroupedShape:
    __slots__ = ("group_paths", "fields")

    def __init__(self, group_paths: List[str], fields: List[GroupedField]):
        self.group_paths = group_paths
        self.fields = fields


# ------------------------------------------------------------------ analysis
def _plain_path(e, allow_id: bool = True) -> Optional[str]:
    """Dotted source path of a pure-PField idiom within the mirror's
    materialized depth (``id`` always allowed — it reads the row-id map)."""
    if not isinstance(e, Idiom):
        return None
    fp = e.field_path()
    if fp is None:
        return None
    if fp == ["id"]:
        return "id" if allow_id else None
    if len(fp) > _depth_limit():
        return None
    return ".".join(fp)


def _field_out_path(f) -> Optional[Tuple[str, ...]]:
    """The output path a projected field writes (None = exotic alias)."""
    from surrealdb_tpu.dbs.iterator import field_display_name

    if f.alias is not None:
        if isinstance(f.alias, Idiom):
            fp = f.alias.field_path()
            return tuple(fp) if fp else None
        return (str(f.alias),)
    if isinstance(f.expr, Idiom):
        fp = f.expr.field_path()
        if fp:
            return tuple(fp)
    return (field_display_name(f.expr),)


def resolve_order_specs(stm) -> Optional[List[OrderSpec]]:
    """Resolve ORDER BY items to SOURCE column paths, honoring how
    `apply_order` keys PROJECTED rows: aliases map back to their source
    expression, paths digging into projected values extend the source path,
    keys no projection produces are constant NONE (dropped — they never
    reorder), and anything ambiguous refuses. None = not lowerable;
    [] = ORDER BY present but provably a no-op."""
    order = getattr(stm, "order", None)
    if not order:
        return []
    if any(getattr(o, "rand", False) for o in order):
        return None
    specs: List[OrderSpec] = []
    if getattr(stm, "value_mode", False):
        f = stm.fields[0]
        if getattr(f, "all", False):
            return None
        src = _plain_path(f.expr)
        if src is None:
            return None
        for o in order:
            parts = o.idiom.field_path() if isinstance(o.idiom, Idiom) else None
            if parts is None:
                return None
            specs.append(OrderSpec(src, o.asc, parts))
        return specs

    star = False
    outs: Dict[Tuple[str, ...], Optional[Tuple[str, ...]]] = {}
    for f in stm.fields:
        if getattr(f, "all", False):
            star = True
            continue
        out = _field_out_path(f)
        if out is None:
            return None
        src = None
        if isinstance(f.expr, Idiom):
            fp = f.expr.field_path()
            if fp:
                src = tuple(fp)
        outs[out] = src
    for o in order:
        parts = o.idiom.field_path() if isinstance(o.idiom, Idiom) else None
        if parts is None:
            return None
        src = _resolve_order_path(tuple(parts), outs, star)
        if src is _UNRESOLVED:
            return None
        if src is None:
            continue  # constant-NONE key: every row ties, stable sort no-op
        if src != ("id",) and len(src) > _depth_limit():
            return None
        specs.append(OrderSpec(".".join(src), o.asc, list(parts)))
    return specs


def _resolve_order_path(op, outs, star):
    if op in outs:
        src = outs[op]
        return src if src is not None else _UNRESOLVED
    for out, src in outs.items():
        if len(out) < len(op) and op[: len(out)] == out:
            # the key digs INTO a projected value: extend the source path
            return _UNRESOLVED if src is None else src + op[len(out):]
        if len(out) > len(op) and out[: len(op)] == op:
            return _UNRESOLVED  # the key is a constructed sub-object
    if star:
        return op
    return None


def resolve_plain_projection(stm) -> Optional[List[Tuple[Any, str]]]:
    """[(field, source path)] when EVERY projected field is a plain path
    readable off the columns (no ``*``, no computed expressions)."""
    if getattr(stm, "value_mode", False):
        f = stm.fields[0]
        if getattr(f, "all", False):
            return None
        p = _plain_path(f.expr)
        return [(f, p)] if p is not None else None
    out = []
    for f in stm.fields:
        if getattr(f, "all", False):
            return None
        p = _plain_path(f.expr)
        if p is None:
            return None
        out.append((f, p))
    return out


def _int_expr_columns(e) -> Set[str]:
    """The column paths of an integer expression (`+`, `-`, `*` of plain
    paths and constants), empty where `e` is no such expression or names no
    column. Pure AST: whether the cells and the constants ARE ints is read
    a statement, from the columns and the bound values (`_bind_expr`)."""
    if isinstance(e, BinaryOp) and e.op in ("+", "-", "*"):
        l, r = _int_expr_columns(e.l), _int_expr_columns(e.r)
        ok_l, ok_r = l or _is_const(e.l), r or _is_const(e.r)
        return (l | r) if ok_l and ok_r else set()
    p = _plain_path(e, allow_id=False)
    return {p} if p is not None else set()


def _bind_expr(ctx, e):
    """An integer expression as a tree of ("col", path) / ("const", int) /
    (op, a, b), its constants evaluated under `ctx`; None where one is no
    int (a float, a decimal, a bool: the row path's arithmetic then)."""
    if isinstance(e, BinaryOp) and e.op in ("+", "-", "*"):
        a, b = _bind_expr(ctx, e.l), _bind_expr(ctx, e.r)
        return None if a is None or b is None else (e.op, a, b)
    p = _plain_path(e, allow_id=False)
    if p is not None:
        return ("col", p)
    v = _const_value(ctx, e)
    return ("const", v) if isinstance(v, int) and not isinstance(v, bool) else None


def _expr_bound(tree, ranges: Dict[str, Tuple[int, int]]) -> Tuple[int, int]:
    """(least, greatest) value the tree can take over columns inside
    `ranges`, by interval arithmetic in Python ints."""
    if tree[0] == "col":
        return ranges[tree[1]]
    if tree[0] == "const":
        return tree[1], tree[1]
    (alo, ahi), (blo, bhi) = _expr_bound(tree[1], ranges), _expr_bound(tree[2], ranges)
    if tree[0] == "+":
        return alo + blo, ahi + bhi
    if tree[0] == "-":
        return alo - bhi, ahi - blo
    ends = (alo * blo, alo * bhi, ahi * blo, ahi * bhi)
    return min(ends), max(ends)


def _tree_columns(tree) -> Set[str]:
    if tree[0] == "col":
        return {tree[1]}
    return set() if tree[0] == "const" else _tree_columns(tree[1]) | _tree_columns(tree[2])


def _polynomial(tree) -> Optional[Dict[Tuple[str, ...], int]]:
    """The tree as a polynomial of its columns: monomial (the sorted paths
    whose product it is, () the constant term) -> coefficient. Which
    monomials there are follows from the tree's shape alone (a coefficient
    that comes out 0 keeps its monomial), only the coefficients from its
    constants. None past MONOMIALS_MAX monomials."""
    if tree[0] == "col":
        return {(tree[1],): 1}
    if tree[0] == "const":
        return {(): tree[1]}
    a, b = _polynomial(tree[1]), _polynomial(tree[2])
    if a is None or b is None:
        return None
    out: Dict[Tuple[str, ...], int] = {}
    if tree[0] == "*":
        for ma, ca in a.items():
            for mb, cb in b.items():
                m = tuple(sorted(ma + mb))
                out[m] = out.get(m, 0) + ca * cb
    else:
        sign = 1 if tree[0] == "+" else -1
        out = dict(a)
        for mb, cb in b.items():
            out[mb] = out.get(mb, 0) + sign * cb
    return out if len(out) <= MONOMIALS_MAX else None


def grouped_shape(stm) -> Optional[GroupedShape]:
    """The statement's GROUP BY shape when every piece lowers: plain-path
    group keys, aggregates from LOWERED_AGGREGATES over plain paths (sum and
    mean also over an integer expression of columns and constants),
    plain-path first-member projections. None otherwise."""
    from surrealdb_tpu.dbs.iterator import _AGGREGATES

    if not (getattr(stm, "group", None) or getattr(stm, "group_all", False)):
        return None
    group_paths: List[str] = []
    for g in getattr(stm, "group", None) or []:
        p = _plain_path(g)
        if p is None:
            return None
        group_paths.append(p)
    fields: List[GroupedField] = []
    for f in stm.fields:
        if getattr(f, "all", False):
            return None
        e = f.expr
        if isinstance(e, FunctionCall) and e.name in _AGGREGATES:
            if e.name == "count" and not e.args:
                fields.append(GroupedField(f, AggSpec("count", None), None))
                continue
            kind = LOWERED_AGGREGATES.get(e.name)
            if kind is None or len(e.args) != 1:
                return None
            ap = _plain_path(e.args[0])
            if ap is None:
                if kind not in ("sum", "mean") or not _int_expr_columns(e.args[0]):
                    return None
                fields.append(GroupedField(f, AggSpec(kind, None, e.args[0]), None))
                continue
            fields.append(
                GroupedField(f, AggSpec("count_arg" if kind == "count" else kind, ap), None)
            )
        elif isinstance(e, Idiom):
            p = _plain_path(e)
            if p is None:
                return None
            fields.append(GroupedField(f, None, p))
        else:
            return None
    return GroupedShape(group_paths, fields)


# ------------------------------------------------------------------ row or columnar
def choose_strategy(mirror, n_rows: int, shape: str) -> Tuple[str, dict]:
    """Row vs columnar for one lowerable statement. Inputs are the mirror's
    state and the pipeline shape; the returned note lands in plan notes so
    EXPLAIN ANALYZE names the decision. Where a columnar grouped statement
    then runs, on the device (one dispatch of ops/column_agg.py over the
    columns' planes in HBM) or on the host (NumPy over the mirror's
    arrays), is `grouped_route`'s one rule; ordered statements run on the
    host."""
    note = {
        "shape": shape,
        "rows": n_rows,
        "mirrored": mirror is not None,
        "min_rows": cnf.COLUMN_MIRROR_MIN_ROWS,
    }
    if n_rows < cnf.COLUMN_MIRROR_MIN_ROWS and mirror is None:
        note["decision"] = "row"
        note["why"] = "below mirror floor"
        return "row", note
    note["decision"] = "columnar"
    return "columnar", note


# ------------------------------------------------------------------ serving
def mirror_floor_ok(ctx, registry, tb: str) -> bool:
    """Never-mirrored tables are only worth mirroring above the row floor —
    the one admission rule column_scan_plan and the pipeline share."""
    from surrealdb_tpu import key as keys
    from surrealdb_tpu.key.encode import prefix_end

    ns, db = ctx.ns_db()
    if registry.get((ns, db, tb)) is not None:
        return True
    pre = keys.thing_prefix(ns, db, tb)
    head = ctx.txn().keys(pre, prefix_end(pre), cnf.COLUMN_MIRROR_MIN_ROWS)
    return len(head) >= cnf.COLUMN_MIRROR_MIN_ROWS


def mirror_for(ctx, tb: str):
    """The table's serveable mirror, respecting the row-count floor for
    never-mirrored tables. None keeps the row path."""
    ns, db = ctx.ns_db()
    registry = getattr(ctx.ds(), "column_mirrors", None)
    if registry is None:
        return None
    if not mirror_floor_ok(ctx, registry, tb):
        return None
    return registry.serveable(ctx, (ns, db, tb))


def _columns_for(mirror, paths: Set[str]):
    """columns_for minus the ``id`` pseudo-path (read off the row-id map)."""
    return mirror.columns_for({p for p in paths if p != "id"})


def survivors(ctx, tb: str, mirror, compiled: Optional[CompiledPredicate], cond, doc_cache):
    """Key-ordered surviving row indices after the WHERE (mask + per-row
    re-check of OTHER-tagged rows against the ORIGINAL cond expression).
    None when the mask cannot serve."""
    n = mirror.n
    if compiled is None:
        keep = np.ones(n, dtype=bool)
    else:
        cols = _columns_for(mirror, compiled.paths)
        if cols is None:
            return None
        mask, needs_row = compiled.evaluate(cols)
        keep = mask & ~needs_row
        fb = np.nonzero(needs_row)[0]
        if fb.size:
            for i in fb:
                ctx.check_deadline()
                doc = _doc(ctx, tb, mirror, int(i), doc_cache)
                if doc is None:
                    continue
                rid = Thing(tb, mirror.ids[int(i)])
                with ctx.with_doc_value(doc, rid=rid) as c:
                    if truthy(cond.compute(c)):
                        keep[int(i)] = True
    order = mirror.key_order()
    if order is None:
        return np.nonzero(keep)[0]
    return order[keep[order]]


# ------------------------------------------------------------------ cells
def _doc(ctx, tb: str, mirror, i: int, cache: dict):
    d = cache.get(i, _MISSING)
    if d is _MISSING:
        ns, db = ctx.ns_db()
        d = ctx.txn().get_record(ns, db, tb, mirror.ids[i])
        cache[i] = d
    return d


def cell_value(ctx, tb: str, mirror, cols, path: str, i: int, doc_cache):
    """One cell's value, exactly as the row path would compute it: scalar
    tags reconstruct from the column planes; OTHER decodes the document
    once and applies get_path (the same function Idiom.compute uses)."""
    if path == "id":
        return Thing(tb, mirror.ids[i])
    col = cols[path]
    t = int(col.tags[i])
    if t == TAG_NONE:
        return NONE
    if t == TAG_NULL:
        # stored NULLs decode as python None (utils/ser); returning the
        # Null singleton would differ byte-wise (and hash-wise in group
        # keys) from the row path's value
        return None
    if t == TAG_BOOL:
        return bool(col.nums[i])
    if t == TAG_INT:
        return int(col.nums[i])
    if t == TAG_FLOAT:
        return float(col.nums[i])
    if t == TAG_STR:
        return col.str_array()[i]
    if t == TAG_DATETIME:
        return Datetime(int(col.i64()[i]))
    doc = _doc(ctx, tb, mirror, i, doc_cache)
    if doc is None:
        return NONE
    return get_path(ctx, doc, [PField(n) for n in path.split(".")])


# ------------------------------------------------------------------ sorting
def order_permutation(
    ctx, tb: str, mirror, cand: np.ndarray, specs: List[OrderSpec],
    doc_cache: dict, value_mode: bool = False,
) -> Optional[np.ndarray]:
    """`cand` (row indices in streaming order) reordered by the ORDER BY
    specs — np.lexsort over numeric key planes when every order cell is a
    scalar tag, the exact `apply_order` stable python sort over
    reconstructed values otherwise. None when columns cannot resolve."""
    if not specs or cand.size <= 1:
        return cand
    cols = _columns_for(mirror, {s.path for s in specs})
    if cols is None:
        return None
    vector = True
    for s in specs:
        if s.path == "id":
            vector = False
            break
        if (cols[s.path].tags[cand] == TAG_OTHER).any():
            vector = False
            break
    if vector:
        return cand[_lexsort_perm(cols, cand, specs)]
    # hybrid: python stable sorts over per-row values (OTHER cells decode
    # their doc once; `id` reads the row-id map) — byte-identical keys
    vals_per_spec: List[List[Any]] = []
    for s in specs:
        vals = []
        for i in cand:
            v = cell_value(ctx, tb, mirror, cols, s.path, int(i), doc_cache)
            if value_mode and isinstance(v, dict) and s.parts:
                # apply_order digs the order idiom into dict-valued rows
                v = get_path(ctx, v, [PField(n) for n in s.parts])
            vals.append(v)
        vals_per_spec.append(vals)
    idx = list(range(cand.size))
    for si in range(len(specs) - 1, -1, -1):
        vals = vals_per_spec[si]
        idx.sort(key=lambda j, _v=vals: sort_key(_v[j]), reverse=not specs[si].asc)
    return cand[np.asarray(idx, dtype=np.int64)]


def _lexsort_perm(cols, cand: np.ndarray, specs: List[OrderSpec]) -> np.ndarray:
    """Stable multi-key argsort reproducing value_cmp: per key a numeric
    (ordinal, nan-rank, within-type) triple; within-type is the value for
    bool/number and a dense np.unique rank for strings/datetimes (equal
    values share a rank, so ties stay ties). DESC negates the triple —
    stable, like python's reverse=True."""
    n = cand.size
    keys: List[np.ndarray] = []
    for s in reversed(specs):
        col = cols[s.path]
        t = col.tags[cand]
        ordv = ORD_OF_TAG[t].astype(np.int64)
        within = np.zeros(n, dtype=np.float64)
        nanflag = np.ones(n, dtype=np.int8)
        num = (t == TAG_BOOL) | (t == TAG_INT) | (t == TAG_FLOAT)
        if num.any():
            v = col.nums[cand][num]
            nan = np.isnan(v)
            within[num] = np.where(nan, 0.0, v)
            nf = nanflag[num]
            nf[nan] = 0
            nanflag[num] = nf
        st = t == TAG_STR
        if st.any():
            sv = col.str_array()[cand][st]
            _, inv = np.unique(sv, return_inverse=True)
            within[st] = inv.astype(np.float64)
        dt = t == TAG_DATETIME
        if dt.any():
            iv = col.i64()[cand][dt]
            _, inv = np.unique(iv, return_inverse=True)
            within[dt] = inv.astype(np.float64)
        if not s.asc:
            ordv, nanflag, within = -ordv, -nanflag, -within
        keys.extend([within, nanflag.astype(np.int64), ordv])
    return np.lexsort(keys)


# ------------------------------------------------------------------ grouping
def _hashable(v):
    from surrealdb_tpu.dbs.iterator import _hashable as _h

    return _h(v)


def factorize(
    ctx, tb: str, mirror, cols, group_paths: List[str], rows: np.ndarray,
    doc_cache: dict,
) -> Tuple[np.ndarray, int]:
    """(inverse group index per row, group count) with groups numbered in
    FIRST-APPEARANCE order (the row path's insertion-ordered dict).
    Vectorized np.unique codes when every key cell is a scalar tag with no
    NaN (python dict equality and the code planes then agree — bool/int/
    float collapse on the value plane exactly like `1 == 1.0 == True`);
    dict factorize over reconstructed values otherwise."""
    n = rows.size
    if not group_paths:
        return np.zeros(n, dtype=np.int64), (1 if n else 0)
    vector = True
    for p in group_paths:
        if p == "id":
            vector = False
            break
        t = cols[p].tags[rows]
        if (t == TAG_OTHER).any():
            vector = False
            break
        num = (t == TAG_INT) | (t == TAG_FLOAT)
        if num.any() and np.isnan(cols[p].nums[rows][num]).any():
            vector = False  # NaN group keys: dict semantics are per-object
            break
    if vector and n:
        planes: List[np.ndarray] = []
        for p in group_paths:
            col = cols[p]
            t = col.tags[rows]
            # class plane: python == collapses bool/int/float — one class
            cls = np.zeros(n, dtype=np.int8)
            cls[t == TAG_NULL] = 1
            cls[(t == TAG_BOOL) | (t == TAG_INT) | (t == TAG_FLOAT)] = 2
            cls[t == TAG_STR] = 3
            cls[t == TAG_DATETIME] = 4
            val = np.zeros(n, dtype=np.float64)
            num = cls == 2
            if num.any():
                # + 0.0 normalizes -0.0 to +0.0: np.unique(axis=0) compares
                # rows BITWISE (void view), while the row path's dict key
                # collapses -0.0 == 0.0 — they must factorize identically
                val[num] = col.nums[rows][num] + 0.0
            st = t == TAG_STR
            if st.any():
                _, inv = np.unique(col.str_array()[rows][st], return_inverse=True)
                val[st] = inv.astype(np.float64)
            dt = t == TAG_DATETIME
            if dt.any():
                _, inv = np.unique(col.i64()[rows][dt], return_inverse=True)
                val[dt] = inv.astype(np.float64)
            planes.extend([cls.astype(np.float64), val])
        stacked = np.stack(planes, axis=1)
        _, inv = np.unique(stacked, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        g = int(inv.max()) + 1
        first = np.full(g, n, dtype=np.int64)
        np.minimum.at(first, inv, np.arange(n, dtype=np.int64))
        rank = np.empty(g, dtype=np.int64)
        rank[np.argsort(first, kind="stable")] = np.arange(g, dtype=np.int64)
        return rank[inv], g
    key2gid: Dict[Any, int] = {}
    inv = np.empty(n, dtype=np.int64)
    for j in range(n):
        i = int(rows[j])
        key = tuple(
            _hashable(cell_value(ctx, tb, mirror, cols, p, i, doc_cache))
            for p in group_paths
        )
        gid = key2gid.setdefault(key, len(key2gid))
        inv[j] = gid
    return inv, len(key2gid)


class _ExprDecline(Exception):
    """An integer-expression aggregate the host arrays cannot answer
    exactly (a cell that is no int, a constant that is no int, a value
    past int64): the statement keeps the row path."""


def _eval_tree(tree, cols, rows: np.ndarray) -> np.ndarray:
    if tree[0] == "col":
        return cols[tree[1]].nums[rows].astype(np.int64)
    if tree[0] == "const":
        return np.int64(tree[1])
    a, b = _eval_tree(tree[1], cols, rows), _eval_tree(tree[2], cols, rows)
    return a + b if tree[0] == "+" else a - b if tree[0] == "-" else a * b


def _segment_expr(ctx, cols, agg: AggSpec, rows: np.ndarray, inv: np.ndarray, g: int) -> List[Any]:
    """sum / mean of an integer expression a group, the row path's Python
    ints: the expression a row in int64 (interval bounds from the columns'
    least and greatest cells prove that no value wraps), the sums by float64
    `bincount` inside the f64-exact window, by an int64 segmented add while
    bound x rows stays under 2**63, in Python ints past that."""
    tree = _bind_expr(ctx, agg.expr)
    if tree is None:
        raise _ExprDecline("constant")
    ranges = {}
    for p in _tree_columns(tree):
        if (cols[p].tags[rows] != TAG_INT).any():
            raise _ExprDecline("cells")
        cells = cols[p].nums[rows]
        ranges[p] = (int(cells.min()), int(cells.max())) if rows.size else (0, 0)
    lo, hi = _expr_bound(tree, ranges)
    bound = max(abs(lo), abs(hi))
    if bound >= 1 << 63:
        raise _ExprDecline("bound")
    vals = np.broadcast_to(_eval_tree(tree, cols, rows), rows.shape)
    counts = np.bincount(inv, minlength=g)
    if bound * rows.size < F64_EXACT_INT:
        sums = [int(x) for x in np.bincount(inv, weights=vals.astype(np.float64), minlength=g)]
    else:
        order = np.argsort(inv, kind="stable")
        at = np.searchsorted(inv[order], np.arange(g + 1))
        if bound * rows.size < 1 << 63:
            sums = [int(x) for x in np.add.reduceat(vals[order], at[:-1])]  # no group is empty
        else:
            sums = [sum(vals[order[at[k] : at[k + 1]]].tolist()) for k in range(g)]
    if agg.kind == "sum":
        return sums
    return [(s_ / int(c)) if c else NONE for s_, c in zip(sums, counts)]


def _group_members(inv: np.ndarray, g: int) -> List[np.ndarray]:
    order = np.argsort(inv, kind="stable")
    bounds = np.searchsorted(inv[order], np.arange(g + 1))
    return [order[bounds[k]:bounds[k + 1]] for k in range(g)]


def segment_aggregate(
    ctx, tb: str, mirror, cols, agg: AggSpec, rows: np.ndarray,
    inv: np.ndarray, g: int, doc_cache: dict,
) -> List[Any]:
    """One aggregate's per-group values, byte-identical to the row path's
    `_eval_aggregate`. Vectorized segment-reduce per group; groups that
    need python semantics (OTHER cells, NaN min/max folds, int sums past
    the f64-exact window) re-fold their reconstructed values exactly."""
    n = rows.size
    if agg.kind == "count":
        return [int(x) for x in np.bincount(inv, minlength=g)]
    if agg.expr is not None:
        return _segment_expr(ctx, cols, agg, rows, inv, g)

    col = cols[agg.path] if agg.path != "id" else None
    if agg.path == "id":
        # id cells are Things: truthy for count, non-numeric for the rest
        if agg.kind == "count_arg":
            return [int(x) for x in np.bincount(inv, minlength=g)]
        return [NONE] * g

    t = col.tags[rows]
    other = t == TAG_OTHER
    has_other = np.bincount(inv[other], minlength=g) > 0 if other.any() else np.zeros(g, dtype=bool)

    if agg.kind == "count_arg":
        ok = np.zeros(n, dtype=bool)
        num = (t == TAG_BOOL) | (t == TAG_INT) | (t == TAG_FLOAT)
        if num.any():
            ok[num] = col.nums[rows][num] != 0.0
        st = t == TAG_STR
        if st.any():
            ok[st] = col.str_array()[rows][st] != ""
        ok |= t == TAG_DATETIME
        counts = np.bincount(inv[ok], minlength=g).astype(np.int64)
        if other.any():
            for j in np.nonzero(other)[0]:
                v = cell_value(ctx, tb, mirror, cols, agg.path, int(rows[j]), doc_cache)
                if truthy(v):
                    counts[inv[j]] += 1
        return [int(x) for x in counts]

    numeric = (t == TAG_INT) | (t == TAG_FLOAT)
    vals = col.nums[rows]
    nan = numeric & np.isnan(vals)
    has_nan = np.bincount(inv[nan], minlength=g) > 0 if nan.any() else np.zeros(g, dtype=bool)
    n_num = np.bincount(inv[numeric], minlength=g)
    is_float = t == TAG_FLOAT
    has_float = (
        np.bincount(inv[is_float], minlength=g) > 0
        if is_float.any()
        else np.zeros(g, dtype=bool)
    )
    members: Optional[List[np.ndarray]] = None

    def python_fold(k: int) -> List[Any]:
        nonlocal members
        if members is None:
            members = _group_members(inv, g)
        out = []
        for j in members[k]:
            v = cell_value(ctx, tb, mirror, cols, agg.path, int(rows[j]), doc_cache)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out.append(v)
        return out

    if agg.kind in ("sum", "mean"):
        w = np.where(numeric, np.where(np.isnan(vals), np.nan, vals), 0.0)
        sums = np.bincount(inv, weights=np.where(numeric, w, 0.0), minlength=g)
        # every intermediate |partial sum| is bounded by sum(|v|): exact
        # int arithmetic is provable inside the f64 window, re-fold outside
        bounds = np.bincount(
            inv, weights=np.where(numeric, np.abs(np.where(np.isnan(vals), 0.0, vals)), 0.0),
            minlength=g,
        )
        out: List[Any] = []
        for k in range(g):
            if has_other[k] or (not has_float[k] and bounds[k] >= _F64_EXACT):
                nums = python_fold(k)
                s: Any = sum(nums)
                cnt = len(nums)
            elif has_float[k]:
                s, cnt = float(sums[k]), int(n_num[k])
            else:
                s, cnt = int(sums[k]), int(n_num[k])
            if agg.kind == "sum":
                out.append(s)
            else:
                out.append((s / cnt) if cnt else NONE)
        return out

    # min / max: value from the FIRST member achieving the fold result so
    # int-vs-float ties keep the row path's type; NaN folds are python's
    # order-dependent semantics — re-fold those groups exactly
    best = np.full(g, np.inf if agg.kind == "min" else -np.inf, dtype=np.float64)
    if numeric.any():
        reduce_at = np.minimum.at if agg.kind == "min" else np.maximum.at
        reduce_at(best, inv[numeric & ~nan], vals[numeric & ~nan])
    first_at = np.full(g, n, dtype=np.int64)
    if numeric.any():
        hit = numeric & ~nan & (vals == best[inv])
        if hit.any():
            np.minimum.at(first_at, inv[hit], np.nonzero(hit)[0])
    out = []
    for k in range(g):
        if has_other[k] or has_nan[k]:
            nums = python_fold(k)
            if agg.kind == "min":
                out.append(min(nums, default=NONE))
            else:
                out.append(max(nums, default=NONE))
            continue
        if not n_num[k]:
            out.append(NONE)
            continue
        j = int(first_at[k])
        v = float(vals[j])
        out.append(int(v) if int(t[j]) == TAG_INT else v)
    return out


# ------------------------------------------------------------------ the device route
# The least table the device route takes: where a launch and a collect stop
# costing more than the host's NumPy pass. One session on a v5e's host (my
# chip runs, PR 40; PERF.md section 6): a device statement is 2.4-2.5 ms at
# any table up to 128,000 rows (2.1 for Q6's shape); the host route's Q1 is
# 2.50 ms at 964 rows, 4.24 at 2,007, 17.1 at 7,982, 336 at 127,998 (its
# five expression sums and four groups: ~2.2 us a row), so Q1's shape crosses
# at ~1,000 rows. A one-sum, one-group statement (Q6's shape) crosses only at
# ~43,000 (host 1.77 ms at 32,082 rows, 5.08 at 127,998); between the two
# floors the device costs such a statement up to 1.5 ms and the host would
# cost a Q1 up to 90, so the lower floor stands.
DEVICE_MIN_ROWS = 1024
PLACEMENTS_MAX = 64  # placements a mirror build keeps (grouped_route)
MONOMIALS_MAX = 16  # distinct products of columns a launch sums: Q1's five expressions expand to six


class DevicePlan:
    """A grouped statement's shape placed on one mirror build: all of a
    launch but its riders' constants. `columns` the (path, form) of each
    plane it reads, in the planes' order, `static` the kernel's shape over
    those planes (`pred`, `keys`, `exprs`: the monomials), `groups`,
    `strides`, `serial` the mirror build the planes belong to and `key` the dispatch key they
    all make; `compared` the column each predicate term compares (a rider's
    constants are placed among ITS values); and how a reply folds into
    rows: `polys`, a summed expression's coefficients (one a monomial of
    `static["exprs"]`, then the constant term's), `slots`, which of
    `polys` a field reads, `keys` the group keys' columns."""

    __slots__ = ("planes", "columns", "static", "groups", "strides", "compared", "polys", "keys", "slots", "rows",
                 "serial", "key")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


def _prepared(route: str, reason: str, t_enter: float, shape: Optional[GroupedShape] = None, rows: int = 0, groups: int = 0) -> None:
    """One grouped statement sent to `route` (`device`, `host` or `row`)
    for `reason`: the labels of its `column_prepare` span, which runs from
    run_pipeline's entry to here (the dispatch submit of a `device`
    statement, the start of the NumPy pass of a `host` one, the decline of
    a `row` one). `column_pipeline{outcome}` counts the same three ways
    out at run_pipeline's own exits."""
    from surrealdb_tpu import tracing

    aggregates = sum(1 for gf in shape.fields if gf.agg is not None) if shape is not None else 0
    tracing.record_span_into(
        tracing.current(), "column_prepare",
        {"route": route, "reason": reason, "rows": rows, "groups": groups, "aggregates": aggregates},
        t_enter, _time.perf_counter() - t_enter,
    )


def _code_constant(d, op: str, c) -> Optional[int]:
    """The int32 a rider compares a plane with under `op`, so that `plane
    op constant'` holds on exactly the rows where `cell op c` does; None
    where `c` is not of the column's kind (the host mask has the cross-type
    rules). A `value` plane takes an int inside int32 as it is; a `code`
    plane takes the constant's place among the sorted distinct values."""
    if d.form == "value":
        if isinstance(c, bool) or not isinstance(c, int) or not -(1 << 31) <= c < (1 << 31):
            return None
        return c
    if d.tag == TAG_STR:
        if not (isinstance(c, str) and type(c) is str):
            return None
    elif d.tag == TAG_DATETIME:
        if not isinstance(c, Datetime):
            return None
        c = c.nanos
    elif d.tag == TAG_BOOL:
        if not isinstance(c, bool):
            return None
        c = int(c)
    elif isinstance(c, bool) or not isinstance(c, int):
        return None
    if op in ("<=", ">"):
        return int(np.searchsorted(d.values, c, side="right")) - 1
    left = int(np.searchsorted(d.values, c, side="left"))
    if op == "=" and not (left < len(d.values) and d.values[left] == c):
        return -1  # codes start at 0: -1 equals none
    return left


def grouped_route(ctx, shape: GroupedShape, compiled: Optional[CompiledPredicate], mirror) -> Tuple[str, str, Any]:
    """THE rule that says where a lowered grouped statement over a serveable
    mirror runs: (`device`, "", (plan, the rider's constants)) or (`host`,
    reason, None).

    `device` where the WHERE is absent or a conjunction of `<`, `<=`, `=`,
    `>=`, `>` between device columns and constants of their kind; the group
    keys are device columns whose distinct counts multiply to at most 256;
    every aggregate is `count()`, or `math::sum` / `math::mean` of a column
    or an integer expression (`+`, `-`, `*`) of int32 columns and integer
    constants whose interval bound times the table's rows stays under
    2**63 and which expand, all together, to at most MONOMIALS_MAX products
    of columns; every other projection is a group key; the device is not
    disabled; and the table has DEVICE_MIN_ROWS rows. A column has a device
    form only if no cell of it is NONE, NULL, a float or OTHER
    (idx/column_mirror.py). Everything else is `host`, today's NumPy route,
    with the reason named.

    What of the rule a statement's predicate constants cannot move (the
    columns, their planes, the shape over them, the bounds) is worked out
    once a (group keys, fields, compared columns and operators, summed
    expressions) and kept with the mirror build it was worked out on
    (`mirror.placements`, gone with the build as its planes are): `_place`.
    A statement binds its expressions' constants, looks its placement up,
    and places its predicate constants among the columns' values."""
    if cnf.TPU_DISABLE:
        return "host", "tpu_disable", None
    if mirror.n < DEVICE_MIN_ROWS:
        return "host", "rows", None
    terms = compiled.device_terms() if compiled is not None else []
    if terms is None:
        return "host", "where", None
    trees: List[Any] = []  # the distinct summed expressions
    slots: List[Optional[int]] = []  # a field's expression, None for a key or the count
    for gf in shape.fields:
        if gf.agg is None:
            if gf.path not in shape.group_paths:
                return "host", "projection", None
            slots.append(None)
        elif gf.agg.kind == "count":
            slots.append(None)
        elif gf.agg.kind in ("sum", "mean"):
            tree = ("col", gf.agg.path) if gf.agg.expr is None else _bind_expr(ctx, gf.agg.expr)
            if tree is None:
                return "host", "constant", None
            if tree not in trees:
                trees.append(tree)
            slots.append(trees.index(tree))
        else:
            return "host", "aggregate", None
    compared = tuple((p, op) for p, op, _ in terms)
    asked = (tuple(shape.group_paths), tuple(slots), compared, tuple(trees))
    plan = mirror.placements.get(asked)
    if plan is None:
        if len(mirror.placements) >= PLACEMENTS_MAX:
            mirror.placements.clear()  # a client's constants inside summed expressions: each value is a placement
        plan = mirror.placements[asked] = _place(shape, compared, trees, slots, mirror)
    if isinstance(plan, str):
        return "host", plan, None
    consts = []
    for d, (_, op, c) in zip(plan.compared, terms):
        cc = _code_constant(d, op, c)
        if cc is None:
            return "host", "constant", None
        consts.append(cc)
    return "device", "", (plan, tuple(consts))


def _place(shape: GroupedShape, compared, trees, slots, mirror):
    """`grouped_route`'s rule past the statement's own constants: the
    DevicePlan of (`compared` (path, operator) terms, summed `trees`,
    `slots`) over `mirror`, or the reason (text) the host takes it."""
    from surrealdb_tpu.ops.column_agg import GROUPS_MAX
    from surrealdb_tpu.utils.num import next_pow2

    summed = set().union(*(_tree_columns(t) for t in trees)) if trees else set()
    polys = [_polynomial(t) for t in trees]
    monos = list(dict.fromkeys(m for poly in polys if poly is not None for m in poly if m))
    if None in polys or len(monos) > MONOMIALS_MAX:
        return "expression"
    want = {(p, "value") for p in summed} | {(p, "code") for p in shape.group_paths}
    want |= {(p, "any") for p, _ in compared if p not in summed}
    if not want:
        return "no_column"  # a bare count(): the mirror's row count answers
    dev, why = mirror.device_columns(want)
    if dev is None:
        return why
    # an `any` column resolved to a form another asker named is that plane, once
    held = {(p, d.form): d for (p, _), d in dev.items()}
    columns = tuple(sorted(held))
    at = {(p, form): columns.index((p, d.form)) for (p, form), d in dev.items()}
    asked_as = [(p, "value") if p in summed else (p, "any") for p, _ in compared]
    sizes = [len(dev[(p, "code")].values) for p in shape.group_paths]
    total = int(np.prod(sizes, dtype=np.int64)) if sizes else 1
    if total > GROUPS_MAX:
        return "groups"
    strides = tuple(int(np.prod(sizes[k + 1 :], dtype=np.int64)) for k in range(len(sizes)))
    ranges = {p: (dev[(p, "value")].lo, dev[(p, "value")].hi) for p in summed}
    for tree in trees:
        lo, hi = _expr_bound(tree, ranges)
        if max(abs(lo), abs(hi)) * mirror.n >= 1 << 63:
            return "bound"
    static = {
        "pred": tuple((at[w], op) for w, (_, op) in zip(asked_as, compared)),
        "keys": tuple(at[(p, "code")] for p in shape.group_paths),
        "exprs": tuple(tuple(at[(p, "value")] for p in m) for m in monos),
    }
    groups = next_pow2(total)
    return DevicePlan(
        planes=tuple(held[w].plane for w in columns), columns=columns, static=static, groups=groups, strides=strides,
        compared=[dev[w] for w in asked_as],
        polys=[[poly.get(m, 0) for m in monos] + [poly.get((), 0)] for poly in polys],
        keys=[dev[(p, "code")] for p in shape.group_paths], slots=list(slots), rows=mirror.n, serial=mirror.serial,
        # everything a launch is apart from its riders' constants: the mirror build, the columns its planes
        # hold (two statements of one shape over different columns are two launches), the shape over them
        key=("colagg", mirror.serial, columns, static["pred"], static["keys"], static["exprs"], groups),
    )


def _device_runner(plan: DevicePlan):
    """The dispatch runner of one bucket (mirror build, columns, statement
    shape): every rider's constants as one [lanes, terms] operand of ONE
    launch of ops/column_agg.py::grouped_aggregate, whatever the constants
    are. The launch hands the device three small operands, the row count,
    the strides and the constants, each its own transfer: one packed
    operand made the launch phase shorter, the batches narrower and the
    rate lower (PERF.md section 6, PR 40)."""

    def run(payloads):
        import zlib

        from surrealdb_tpu import compile_log
        from surrealdb_tpu.ops.column_agg import grouped_aggregate, unpack_results
        from surrealdb_tpu.utils.num import count_lanes

        static, groups = plan.static, plan.groups
        n_rows, strides = np.int32(plan.rows), np.asarray(plan.strides or (0,), dtype=np.int32)
        consts = np.zeros((count_lanes(len(payloads)), max(len(static["pred"]), 1)), dtype=np.int32)
        for i, mine in enumerate(payloads):
            consts[i, : len(mine)] = mine
        sig = zlib.crc32(repr(sorted(static.items())).encode())
        key = (plan.planes[0].shape[0], consts.shape[0], groups, len(plan.planes), sig)
        with compile_log.tracked("column_agg", key):
            out = grouped_aggregate(plan.planes, n_rows, consts, strides, groups=groups, **static)

        def collect():
            return unpack_results(np.asarray(out), len(payloads), groups, len(static["exprs"]))

        collect.launch_labels = {"rows": plan.rows}
        collect.outputs = (out,)
        return collect

    return run


def _run_grouped_device(ctx, stm, shape: GroupedShape, plan: DevicePlan, consts, t_enter: float, stages) -> List[Any]:
    """One grouped statement as ONE rider of one dispatch: submit its
    constants under the plan's key, fold what comes back into the rows the
    host route makes (groups in first-appearance order, Python-int sums,
    mean = sum / count by the same division)."""
    from surrealdb_tpu import tracing

    _prepared("device", "", t_enter, shape, plan.rows, plan.groups)
    t0 = _time.perf_counter()
    counts, first, sums = ctx.ds().dispatch.submit(plan.key, consts, _device_runner(plan), depth=SWEEP_DEPTH)
    t_op = _time.perf_counter()  # the device operator has returned: the `materialise` span starts here
    stages["device"] = {"rows": plan.rows, "ms": round((t_op - t0) * 1e3, 3)}
    present = np.flatnonzero(counts > 0)
    present = present[np.argsort(first[present], kind="stable")]
    live, n = present.tolist(), counts.tolist()

    def fold(poly) -> List[int]:
        """An expression's sum a present group: its coefficients'
        combination of the monomials' sums and the count, modulo 2**64,
        read as signed (the route rule proved the true sum inside int64)."""
        *coeffs, constant = poly
        terms = [(c, mono) for c, mono in zip(coeffs, sums) if c]
        out = []
        for g in live:
            v = (sum(c * mono[g] for c, mono in terms) + constant * n[g]) & ((1 << 64) - 1)
            out.append(v - (1 << 64) if v >> 63 else v)
        return out

    totals = [fold(poly) for poly in plan.polys]
    per_field: List[List[Any]] = []
    for gf, slot in zip(shape.fields, plan.slots):
        if gf.agg is None:
            k = shape.group_paths.index(gf.path)
            d, stride = plan.keys[k], plan.strides[k]
            codes = (present // stride) % len(d.values)
            per_field.append([_key_cell(d, int(c)) for c in codes])
        elif gf.agg.kind == "count":
            per_field.append([n[g] for g in live])
        elif gf.agg.kind == "sum":
            per_field.append(list(totals[slot]))
        else:
            per_field.append([t / n[g] for t, g in zip(totals[slot], live)])
    stages["reduce"] = {"groups": int(present.size), "rows": int(counts.sum())}
    out = _grouped_rows(ctx, stm, shape, per_field, int(present.size), stages)
    tracing.record_span_into(tracing.current(), "materialise", {}, t_op, _time.perf_counter() - t_op)
    return out


def _key_cell(d, code: int):
    """A group key's value from its code, typed as `cell_value` types it."""
    v = d.values[code]
    if d.tag == TAG_STR:
        return v
    if d.tag == TAG_DATETIME:
        return Datetime(int(v))
    return bool(v) if d.tag == TAG_BOOL else int(v)


# ------------------------------------------------------------------ analysis ladder
class Lowering:
    """One statement's resolved whole-pipeline lowering (grouped_shape OR
    order specs + plain projection, plus the compiled WHERE)."""

    __slots__ = ("shape", "specs", "proj", "compiled", "cond")

    def __init__(self, shape, specs, proj, compiled, cond):
        self.shape = shape
        self.specs = specs
        self.proj = proj
        self.compiled = compiled
        self.cond = cond


def analyze_select(ctx, stm, tb: str) -> Tuple[Optional[Lowering], Optional[str]]:
    """The ONE decline ladder run_pipeline and explain_pipeline share, so
    EXPLAIN can never describe a plan the executor would not take.
    Returns (lowering, None) or (None, counted-decline-reason | None for
    not-pipeline-shaped-at-all). Pure-AST shape checks run before any
    ctx-dependent work (predicate compile, index lookup). The index probe
    here discards its plan and the planner rebuilds it on decline — an
    accepted cost: lowered statements skip the planner entirely, and only
    indexed order/group/limit statements pay the duplicate probe."""
    from surrealdb_tpu.iam.check import perms_apply

    if not cnf.COLUMN_MIRROR:
        return None, None
    if not (
        getattr(stm, "order", None)
        or getattr(stm, "group", None)
        or getattr(stm, "group_all", False)
        or stm.limit is not None
        or stm.start is not None
    ):
        return None, None  # nothing past the mask: the scan plan covers it
    with_ = getattr(stm, "with_", None)
    if with_ is not None and getattr(with_, "noindex", False):
        return None, None
    for attr in ("split", "fetch", "omit"):
        if getattr(stm, attr, None):
            return None, f"decline_{attr}"

    shape = grouped_shape(stm)
    ordered_proj = None
    specs: Optional[List[OrderSpec]] = None
    if shape is None:
        if getattr(stm, "group", None) or getattr(stm, "group_all", False):
            return None, "decline_group"
        specs = resolve_order_specs(stm)
        if specs is None:
            return None, "decline_order"
        ordered_proj = resolve_plain_projection(stm)
        if ordered_proj is None:
            # the SORTED ColumnScanPlan covers doc-projected shapes; the
            # fast path only pays when projections read off the columns
            return None, "decline_projection"

    if perms_apply(ctx):
        return None, "decline_perms"
    cond = getattr(stm, "cond", None)
    compiled = None
    if cond is not None:
        compiled = compile_where(ctx, cond)
        if compiled is None:
            return None, "decline_where"
    # an index-served WHERE narrows candidates far below the mirror scan —
    # defer to the planner (its plans + the row postprocess stay exact)
    from surrealdb_tpu.idx.planner import _build_index_plan

    if _build_index_plan(ctx, stm, tb, with_) is not None:
        return None, "decline_indexed"
    return Lowering(shape, specs, ordered_proj, compiled, cond), None


# ------------------------------------------------------------------ execution
def run_pipeline(ctx, stm, tb: str) -> Optional[Tuple[List[Any], dict]]:
    """Execute one fully-lowerable SELECT over the column mirror. Returns
    (rows, stage notes) or None (decline — reason already counted).

    When `stm` is a plan-cache template with a validated pipeline route,
    the cached Lowering is served instead of re-running analyze_select:
    the shape/order/projection resolution and the duplicate index probe
    are skipped, and only the compiled mask program's CONSTANTS re-bind
    against the live context (predicates.CompiledPredicate.rebind)."""
    from surrealdb_tpu.dbs.plan_cache import active_plan_cache

    pc = active_plan_cache(ctx)
    cached = pc.lowering_for(ctx, stm) if pc is not None else None
    t_enter = _time.perf_counter()
    grouped = bool(getattr(stm, "group", None) or getattr(stm, "group_all", False))

    def declined(reason: Optional[str]):
        """The row path takes the statement: counted, and said on a grouped
        statement's `column_prepare` span."""
        if reason is not None:
            _outcome(reason)
            if grouped:
                _prepared("row", reason, t_enter)
        return None

    low = None
    if cached is not None:
        low = cached
        if low.compiled is not None:
            rb = low.compiled.rebind(ctx)
            if rb is None:
                # a re-derived constant fell outside the lowerable
                # fragment: this serve must re-analyze cold
                low = cached = None
            else:
                low = Lowering(low.shape, low.specs, low.proj, rb, low.cond)
    if low is None:
        low, reason = analyze_select(ctx, stm, tb)
        if low is None:
            return declined(reason)
        if pc is not None:
            pc.install_pipeline(ctx, stm, low)
    shape, specs, ordered_proj = low.shape, low.specs, low.proj
    compiled, cond = low.compiled, low.cond

    mirror = mirror_for(ctx, tb)
    strategy, cost_note = choose_strategy(
        mirror, mirror.n if mirror is not None else 0,
        "grouped" if shape is not None else "ordered",
    )
    if mirror is None or strategy != "columnar":
        return declined("decline_mirror")

    from surrealdb_tpu import telemetry

    doc_cache: dict = {}
    stages: Dict[str, dict] = {}
    route = "host"
    if shape is not None:
        route, reason, placed = grouped_route(ctx, shape, compiled, mirror)
    if route == "device":
        out = _run_grouped_device(ctx, stm, shape, *placed, t_enter, stages)
    else:
        if shape is not None:
            _prepared("host", reason, t_enter, shape, mirror.n)
        t0 = _time.perf_counter()
        rows_idx = survivors(ctx, tb, mirror, compiled, cond, doc_cache)
        if rows_idx is None:
            _outcome("decline_columns")
            return None
        stages["mask"] = {
            "rows": int(rows_idx.size), "ms": round((_time.perf_counter() - t0) * 1e3, 3),
        }
        if shape is not None:
            out = _run_grouped(ctx, stm, tb, mirror, shape, rows_idx, doc_cache, stages)
        else:
            out = _run_ordered(ctx, stm, tb, mirror, specs, ordered_proj, rows_idx, doc_cache, stages)
        if out is None:
            return None
    telemetry.inc(
        "column_pipeline",
        outcome="device" if route == "device" else "grouped" if shape is not None else "ordered",
    )
    # a columnar pipeline examines every mirrored row — it is a full scan
    # in columnar clothing, so the tenant meter sees the same rows_scanned
    # the iterator path would have tallied
    from surrealdb_tpu import accounting

    accounting.tally(rows_scanned=float(mirror.n))
    note = {
        "table": tb,
        "plan": "ColumnPipeline",
        "strategy": "columnar-pipeline",
        "route": route,
        "cost": cost_note,
        "stages": stages,
    }
    if compiled is not None:
        note["predicate"] = compiled.source
    telemetry.note_plan(note)
    return out, note


def _run_ordered(ctx, stm, tb, mirror, specs, proj, rows_idx, doc_cache, stages):
    from surrealdb_tpu.dbs.iterator import _as_int, project_fields

    t0 = _time.perf_counter()
    ordered = order_permutation(
        ctx, tb, mirror, rows_idx, specs, doc_cache,
        value_mode=getattr(stm, "value_mode", False),
    )
    if ordered is None:
        _outcome("decline_columns")
        return None
    stages["sort"] = {
        "rows": int(ordered.size),
        "keys": [s.path for s in specs],
        "ms": round((_time.perf_counter() - t0) * 1e3, 3),
    }
    start = _as_int(stm.start.compute(ctx), "START") if stm.start is not None else 0
    if stm.limit is not None:
        limit = _as_int(stm.limit.compute(ctx), "LIMIT")
        ordered = ordered[start : start + limit]
    elif start:
        ordered = ordered[start:]

    t0 = _time.perf_counter()
    cols = _columns_for(mirror, {p for _, p in proj if p != "id"})
    if cols is None:
        _outcome("decline_columns")
        return None
    value_mode = getattr(stm, "value_mode", False)
    out: List[Any] = []
    fetched = 0
    for i in ordered:
        i = int(i)
        ctx.check_deadline()
        fallback = False
        for _, p in proj:
            if p != "id" and int(cols[p].tags[i]) == TAG_OTHER:
                fallback = True
                break
        if fallback:
            # a projected cell the columns cannot reproduce: decode the doc
            # once and run the ordinary row-path projection for this row
            doc = _doc(ctx, tb, mirror, i, doc_cache)
            if doc is None:
                continue
            fetched += 1
            rid = Thing(tb, mirror.ids[i])
            with ctx.with_doc_value(doc, rid=rid) as c:
                out.append(project_fields(c, stm.fields, doc, rid, value_mode))
            continue
        if value_mode:
            out.append(cell_value(ctx, tb, mirror, cols, proj[0][1], i, doc_cache))
        else:
            row: dict = {}
            for f, p in proj:
                _assign(ctx, row, f, cell_value(ctx, tb, mirror, cols, p, i, doc_cache))
            out.append(row)
    stages["materialize"] = {
        "rows": len(out), "docs": fetched,
        "ms": round((_time.perf_counter() - t0) * 1e3, 3),
    }
    return out


def _run_grouped(ctx, stm, tb, mirror, shape, rows_idx, doc_cache, stages):
    cols = _columns_for(mirror, _shape_paths(shape))
    if cols is None:
        _outcome("decline_columns")
        return None
    t0 = _time.perf_counter()
    inv, g = factorize(ctx, tb, mirror, cols, shape.group_paths, rows_idx, doc_cache)
    if g == 0:
        stages["reduce"] = {"groups": 0, "ms": 0.0}
        return []  # GROUP over zero members yields no groups (row path)
    first_at = np.full(g, rows_idx.size, dtype=np.int64)
    np.minimum.at(first_at, inv, np.arange(rows_idx.size, dtype=np.int64))
    per_field: List[List[Any]] = []
    for gf in shape.fields:
        if gf.agg is not None:
            try:
                per_field.append(
                    segment_aggregate(ctx, tb, mirror, cols, gf.agg, rows_idx, inv, g, doc_cache)
                )
            except _ExprDecline:
                _outcome("decline_columns")
                return None
        else:
            vals = []
            for k in range(g):
                i = int(rows_idx[int(first_at[k])])
                vals.append(cell_value(ctx, tb, mirror, cols, gf.path, i, doc_cache))
            per_field.append(vals)
    stages["reduce"] = {
        "groups": g, "rows": int(rows_idx.size),
        "ms": round((_time.perf_counter() - t0) * 1e3, 3),
    }
    return _grouped_rows(ctx, stm, shape, per_field, g, stages)


def _grouped_rows(ctx, stm, shape, per_field, g: int, stages):
    """The result rows of a grouped statement from its per-field values (a
    list a field, a value a group, groups in first-appearance order), then
    the statement's ORDER BY and START / LIMIT: the one tail of the host and
    the device route."""
    from surrealdb_tpu.dbs.iterator import apply_order, apply_start_limit

    t0 = _time.perf_counter()
    out: List[Any] = []
    for k in range(g):
        row: dict = {}
        for gf, vals in zip(shape.fields, per_field):
            _assign(ctx, row, gf.field, vals[k])
        out.append(row)
    if getattr(stm, "order", None):
        out = apply_order(ctx, out, stm.order)
    out = apply_start_limit(ctx, out, stm.start, stm.limit)
    stages["materialize"] = {
        "rows": len(out), "ms": round((_time.perf_counter() - t0) * 1e3, 3),
    }
    return out


def _shape_paths(shape: GroupedShape) -> Set[str]:
    """Every column path a grouped shape reads."""
    paths: Set[str] = set(shape.group_paths)
    for gf in shape.fields:
        if gf.agg is None:
            paths.add(gf.path)
        elif gf.agg.expr is not None:
            paths |= _int_expr_columns(gf.agg.expr)
        elif gf.agg.path is not None:
            paths.add(gf.agg.path)
    return paths


def _assign(ctx, row: dict, f, v) -> None:
    from surrealdb_tpu.dbs.iterator import _assign_field

    _assign_field(ctx, row, f, v)


# ------------------------------------------------------------------ explain
def explain_pipeline(ctx, stm, tb: str) -> Optional[dict]:
    """Static plan description for EXPLAIN (no execution): the SAME
    analyze_select ladder the executor runs, so EXPLAIN never describes a
    plan run_pipeline would decline (outcome counters stay the executor's
    alone). None when the statement would not take the fast path."""
    low, _reason = analyze_select(ctx, stm, tb)
    if low is None:
        return None
    detail: dict = {"strategy": "columnar-pipeline"}
    if low.compiled is not None:
        detail["predicate"] = low.compiled.source
    if low.shape is not None:
        detail["stages"] = ["mask", "factorize", "segment-reduce", "materialize"]
        detail["group"] = low.shape.group_paths or ["ALL"]
        detail["aggregates"] = [
            f"{gf.agg.kind}({gf.agg.path or (repr(gf.agg.expr) if gf.agg.expr is not None else '')})"
            for gf in low.shape.fields
            if gf.agg
        ]
    else:
        detail["stages"] = ["mask", "sort", "limit", "materialize"]
        detail["order"] = [
            {"key": s.path, "direction": "ASC" if s.asc else "DESC"}
            for s in low.specs
        ]
    if mirror_for(ctx, tb) is None:
        return None
    return detail


# ------------------------------------------------------------------ cluster partials
def _row_partials(ctx, tb: str, stm, shape: GroupedShape, owner_ok) -> dict:
    """Row-scan twin of the columnar partial computation (shard mirror not
    serveable): exact by construction — it IS the row path, accumulated
    into the same partial shapes."""
    from surrealdb_tpu.dbs.iterator import scan_table
    from surrealdb_tpu.key.encode import enc_value_key

    cond = getattr(stm, "cond", None)
    group_idioms = getattr(stm, "group", None) or []
    groups: Dict[Any, dict] = {}
    rows_seen = 0
    for rid, doc in scan_table(ctx, tb):
        if owner_ok is not None and not owner_ok(rid):
            continue
        with ctx.with_doc_value(doc, rid=rid) as c:
            if cond is not None and not truthy(cond.compute(c)):
                continue
            rows_seen += 1
            key = tuple(_hashable(g.compute(c)) for g in group_idioms)
            grp = groups.get(key)
            if grp is None:
                grp = groups[key] = {
                    "key": [g.compute(c) for g in group_idioms],
                    "first_key": bytes(enc_value_key(rid.id)),
                    "firsts": [
                        gf.field.expr.compute(c) if gf.agg is None else None
                        for gf in shape.fields
                    ],
                    "n": 0,
                    "aggs": [
                        (0 if gf.agg and gf.agg.kind in ("count", "count_arg")
                         else {"v": 0, "n": 0, "float": False, "nan": False}
                         if gf.agg else None)
                        for gf in shape.fields
                    ],
                }
            grp["n"] += 1
            for idx, gf in enumerate(shape.fields):
                if gf.agg is None:
                    continue
                kind = gf.agg.kind
                if kind == "count":
                    grp["aggs"][idx] += 1
                    continue
                v = gf.field.expr.args[0].compute(c)
                if kind == "count_arg":
                    if truthy(v):
                        grp["aggs"][idx] += 1
                    continue
                if not (isinstance(v, (int, float)) and not isinstance(v, bool)):
                    continue
                acc = grp["aggs"][idx]
                if isinstance(v, float):
                    acc["float"] = True
                    if v != v:
                        acc["nan"] = True
                if kind in ("sum", "mean"):
                    acc["v"] = v if acc["n"] == 0 else acc["v"] + v
                elif acc["n"] == 0:
                    acc["v"] = v
                elif kind == "min":
                    if v < acc["v"]:
                        acc["v"] = v
                else:
                    if v > acc["v"]:
                        acc["v"] = v
                acc["n"] += 1
    exact = True
    out = list(groups.values())
    for grp in out:
        for gf, acc in zip(shape.fields, grp["aggs"]):
            if gf.agg is None or not isinstance(acc, dict):
                continue
            if gf.agg.kind in ("sum", "mean") and acc["float"]:
                exact = False
            if gf.agg.kind in ("min", "max"):
                if acc["nan"]:
                    exact = False
                if acc["n"] == 0:
                    acc["v"] = NONE
    return {"groups": out, "exact": exact, "rows": rows_seen}


def partial_aggregate(
    ctx, tb: str, stm, owner_ok=None,
) -> Optional[dict]:
    """Per-shard partial aggregates for the cluster pushdown: groups with
    exact-mergeable partials plus the per-group first member's encoded
    record key (the coordinator's global group order and first-member
    tiebreak). `owner_ok(rid)` restricts to rows this shard is responsible
    for under replication. Returns {"groups": [...], "exact": bool};
    columnar over the shard's mirror when it serves, the row-scan twin
    otherwise. A shard that cannot prove byte-exact mergeability (float
    sums, NaN min/max folds) reports exact=False and the coordinator falls
    back to the full gather-and-replay scatter. None = shape decline."""
    shape = grouped_shape(stm)
    if shape is None or any(gf.agg is not None and gf.agg.expr is not None for gf in shape.fields):
        return None  # an expression aggregate has no partial form: the full scatter answers
    out = _columnar_partials(ctx, tb, stm, shape, owner_ok)
    if out is not None:
        return out
    return _row_partials(ctx, tb, stm, shape, owner_ok)


def _columnar_partials(ctx, tb: str, stm, shape: GroupedShape, owner_ok) -> Optional[dict]:
    from surrealdb_tpu.key.encode import enc_value_key

    cond = getattr(stm, "cond", None)
    compiled = None
    if cond is not None:
        compiled = compile_where(ctx, cond)
        if compiled is None:
            return None
    mirror = mirror_for(ctx, tb)
    if mirror is None:
        return None
    doc_cache: dict = {}
    rows_idx = survivors(ctx, tb, mirror, compiled, cond, doc_cache)
    if rows_idx is None:
        return None
    if owner_ok is not None and rows_idx.size:
        keep = np.fromiter(
            (owner_ok(Thing(tb, mirror.ids[int(i)])) for i in rows_idx),
            dtype=bool, count=rows_idx.size,
        )
        rows_idx = rows_idx[keep]
    paths: Set[str] = set(shape.group_paths)
    agg_paths: Set[str] = set()
    for gf in shape.fields:
        if gf.agg is not None and gf.agg.path is not None:
            paths.add(gf.agg.path)
            agg_paths.add(gf.agg.path)
        elif gf.path is not None:
            paths.add(gf.path)
    cols = _columns_for(mirror, paths)
    if cols is None:
        return None
    inv, g = factorize(ctx, tb, mirror, cols, shape.group_paths, rows_idx, doc_cache)
    exact = True
    partials_per_field: List[List[Any]] = []
    counts = np.bincount(inv, minlength=g) if g else np.zeros(0, dtype=np.int64)
    for gf in shape.fields:
        if gf.agg is None:
            partials_per_field.append([None] * g)
            continue
        kind = gf.agg.kind
        if kind in ("count", "count_arg"):
            partials_per_field.append(
                segment_aggregate(ctx, tb, mirror, cols, gf.agg, rows_idx, inv, g, doc_cache)
            )
            continue
        # numeric folds: compute locally-exact values plus the flags the
        # coordinator needs to prove the merge stays byte-exact. A mean's
        # partial is its exact SUM (the merge divides by the merged count).
        local = AggSpec("sum", gf.agg.path) if kind == "mean" else gf.agg
        vals = segment_aggregate(ctx, tb, mirror, cols, local, rows_idx, inv, g, doc_cache)
        flags = _numeric_flags(ctx, tb, mirror, cols, gf.agg, rows_idx, inv, g, doc_cache)
        if kind in ("sum", "mean") and any(f["float"] for f in flags):
            exact = False  # float addition is order-dependent across shards
        if kind in ("min", "max") and any(f["nan"] for f in flags):
            exact = False  # python's NaN fold is order-dependent
        merged = []
        for k in range(g):
            entry = {"v": vals[k], "n": flags[k]["n"]}
            entry.update(flags[k])
            merged.append(entry)
        partials_per_field.append(merged)
    first_at = np.full(g, rows_idx.size, dtype=np.int64)
    if g:
        np.minimum.at(first_at, inv, np.arange(rows_idx.size, dtype=np.int64))
    groups = []
    for k in range(g):
        i = int(rows_idx[int(first_at[k])])
        key_vals = [
            cell_value(ctx, tb, mirror, cols, p, i, doc_cache)
            for p in shape.group_paths
        ]
        firsts = [
            cell_value(ctx, tb, mirror, cols, gf.path, i, doc_cache)
            if gf.agg is None
            else None
            for gf in shape.fields
        ]
        groups.append(
            {
                "key": key_vals,
                "first_key": bytes(enc_value_key(mirror.ids[i])),
                "firsts": firsts,
                "n": int(counts[k]),
                "aggs": [pf[k] for pf in partials_per_field],
            }
        )
    return {"groups": groups, "exact": exact, "rows": int(rows_idx.size)}


def _numeric_flags(ctx, tb, mirror, cols, agg, rows, inv, g, doc_cache):
    """Per-group mergeability evidence for one numeric aggregate: numeric
    member count, float-contributor and NaN flags (OTHER cells decode and
    classify exactly)."""
    col = cols[agg.path] if agg.path != "id" else None
    out = [{"n": 0, "float": False, "nan": False} for _ in range(g)]
    if col is None:
        return out
    t = col.tags[rows]
    numeric = (t == TAG_INT) | (t == TAG_FLOAT)
    vals = col.nums[rows]
    for k, c in enumerate(np.bincount(inv[numeric], minlength=g)):
        out[k]["n"] = int(c)
    fl = t == TAG_FLOAT
    if fl.any():
        for k in np.unique(inv[fl]):
            out[int(k)]["float"] = True
    nan = numeric & np.isnan(vals)
    if nan.any():
        for k in np.unique(inv[nan]):
            out[int(k)]["nan"] = True
    other = t == TAG_OTHER
    for j in np.nonzero(other)[0]:
        v = cell_value(ctx, tb, mirror, cols, agg.path, int(rows[j]), doc_cache)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            k = int(inv[j])
            out[k]["n"] += 1
            if isinstance(v, float):
                out[k]["float"] = True
                if v != v:
                    out[k]["nan"] = True
    return out


def merge_partials(shape: GroupedShape, shard_partials: List[dict]) -> Optional[List[dict]]:
    """Fold per-shard partial-aggregate groups into final per-group field
    values (pre-projection). Shards are folded in ascending first-member
    key order per group so int-before-float ties keep the single-node
    first-member semantics; a tie between EQUAL int and float partials from
    different shards cannot be ordered byte-exactly — return None and let
    the coordinator fall back to the full replay."""
    merged: Dict[Any, dict] = {}
    for part in shard_partials:
        for grp in part["groups"]:
            key = tuple(_hashable(v) for v in grp["key"])
            cur = merged.get(key)
            if cur is None:
                merged[key] = dict(grp)
                continue
            a_first = cur["first_key"] <= grp["first_key"]
            lo, hi = (cur, grp) if a_first else (grp, cur)
            folded = {
                "key": lo["key"],
                "first_key": lo["first_key"],
                "firsts": lo["firsts"],
                "n": lo["n"] + hi["n"],
                "aggs": [],
            }
            for gf, pa, pb in zip(shape.fields, lo["aggs"], hi["aggs"]):
                if gf.agg is None:
                    folded["aggs"].append(None)
                    continue
                kind = gf.agg.kind
                if kind in ("count", "count_arg"):
                    folded["aggs"].append(int(pa) + int(pb))
                    continue
                fa, fb = dict(pa), dict(pb)
                if kind in ("sum", "mean"):
                    fa["v"] = fa["v"] + fb["v"] if fb["n"] else fa["v"]
                    if not fa["n"]:
                        fa["v"] = fb["v"]
                    fa["n"] += fb["n"]
                    fa["float"] = fa["float"] or fb["float"]
                    folded["aggs"].append(fa)
                    continue
                # min/max: fold the two partial values in first-key order —
                # python's fold keeps the earlier value on ties, matching
                # the single-node first-member rule, UNLESS the tied values
                # disagree on int vs float (unprovable without row order)
                va, vb = fa["v"], fb["v"]
                if not fb["n"]:
                    folded["aggs"].append(fa)
                    continue
                if not fa["n"]:
                    fb_all = dict(fb)
                    folded["aggs"].append(fb_all)
                    continue
                if va == vb and repr(va) != repr(vb):
                    # cross-shard tie between ==-equal but byte-distinct
                    # values (2 vs 2.0, -0.0 vs 0.0): the single-node fold
                    # keeps the first in ROW order, unknowable here — refuse
                    return None
                if kind == "min":
                    v = vb if vb < va else va
                else:
                    v = vb if vb > va else va
                fa["v"] = v
                fa["n"] += fb["n"]
                folded["aggs"].append(fa)
            merged[key] = folded
    out = sorted(merged.values(), key=lambda grp: grp["first_key"])
    final: List[dict] = []
    for grp in out:
        vals = []
        for gf, pa in zip(shape.fields, grp["aggs"]):
            if gf.agg is None:
                vals.append(None)
            elif gf.agg.kind in ("count", "count_arg"):
                vals.append(int(pa))
            elif gf.agg.kind == "mean":
                vals.append((pa["v"] / pa["n"]) if pa["n"] else NONE)
            elif gf.agg.kind == "sum":
                vals.append(pa["v"])
            else:
                vals.append(pa["v"] if pa["n"] else NONE)
        final.append({"firsts": grp["firsts"], "values": vals, "n": grp["n"]})
    return final
