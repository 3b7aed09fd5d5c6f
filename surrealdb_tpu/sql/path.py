"""Idiom (path) evaluation: `a.b[3]->likes->person[WHERE age > 2].name`.

Role of the reference's idiom machinery (reference: core/src/sql/idiom.rs,
part.rs, graph.rs, and the 33 value-operation files in sql/value/ — get.rs,
set.rs, del.rs...). A Part::Graph hop scans the graph-pointer keyspace written
by RELATE (see doc/edges: endpoint --Out--> edge, edge --In/Out--> endpoints),
so `->knows->person` is: OUT-scan from the current ids over edge-table
`knows`, then OUT-scan from those edge ids restricted to table `person`.

The batched TPU frontier path (idx/graph) plugs in underneath `graph_hop` for
large frontiers; the semantics here are the per-record reference behavior.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence, Tuple

from surrealdb_tpu import key as keys
from surrealdb_tpu.err import TypeError_
from .value import (
    NONE,
    Null,
    Range,
    Thing,
    escape_ident,
    is_none,
    is_nullish,
    truthy,
    value_eq,
)
from .ast import Expr


# ------------------------------------------------------------------- parts
class Part:
    __slots__ = ()


class PStart(Part):
    __slots__ = ("expr",)

    def __init__(self, expr: Expr):
        self.expr = expr

    def __repr__(self):
        return repr(self.expr)


class PField(Part):
    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __repr__(self):
        return f".{escape_ident(self.name)}"


class PIndex(Part):
    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i

    def __repr__(self):
        return f"[{self.i}]"


class PAll(Part):
    def __repr__(self):
        return "[*]"


class PLast(Part):
    def __repr__(self):
        return "[$]"


class PFlatten(Part):
    def __repr__(self):
        return "…"


class POptional(Part):
    def __repr__(self):
        return "?"


class PWhere(Part):
    __slots__ = ("cond",)

    def __init__(self, cond: Expr):
        self.cond = cond

    def __repr__(self):
        return f"[WHERE {self.cond!r}]"


class PValue(Part):
    __slots__ = ("expr",)

    def __init__(self, expr: Expr):
        self.expr = expr

    def __repr__(self):
        return f"[{self.expr!r}]"


class PMethod(Part):
    """.method(args) — value method / closure-field call."""

    __slots__ = ("name", "args")

    def __init__(self, name: str, args: List[Expr]):
        self.name = name
        self.args = args

    def __repr__(self):
        return f".{self.name}(" + ", ".join(repr(a) for a in self.args) + ")"


class PDestructure(Part):
    """.{ a, b: b.c } — object destructuring projection."""

    __slots__ = ("fields",)

    def __init__(self, fields: List[Tuple[str, Optional[List[Part]]]]):
        self.fields = fields

    def __repr__(self):
        inner = ", ".join(k for k, _ in self.fields)
        return ".{" + inner + "}"


class PGraph(Part):
    """->table / <-table / <->table, with optional (tables.. WHERE cond AS alias)."""

    __slots__ = ("dir", "what", "cond", "alias", "expr_fields")

    def __init__(self, dir_: str, what: List[str], cond: Optional[Expr] = None, alias=None):
        self.dir = dir_  # 'out' | 'in' | 'both'
        self.what = what  # table names; empty = ? (any)
        self.cond = cond
        self.alias = alias

    def __repr__(self):
        arrow = {"out": "->", "in": "<-", "both": "<->"}[self.dir]
        what = "?" if not self.what else ",".join(self.what)
        if self.cond is not None:
            return f"{arrow}({what} WHERE {self.cond!r})"
        return f"{arrow}{what}"


class PRecurse(Part):
    """Recursion bounds `{min..max}` applied to the following path segment
    (reference IDIOM_RECURSION_LIMIT cnf/mod.rs:97)."""

    __slots__ = ("min", "max", "parts")

    def __init__(self, min_: int, max_: Optional[int], parts: List[Part]):
        self.min = min_
        self.max = max_
        self.parts = parts

    def __repr__(self):
        rng = f"{self.min}..{self.max if self.max is not None else ''}"
        return "{" + rng + "}" + "".join(repr(p) for p in self.parts)


# ------------------------------------------------------------------- idiom
class Idiom(Expr):
    __slots__ = ("parts",)

    def __init__(self, parts: List[Part]):
        self.parts = parts

    def compute(self, ctx):
        parts = self.parts
        if not parts:
            return NONE
        first = parts[0]
        if isinstance(first, PStart):
            start = first.expr.compute(ctx)
            return get_path(ctx, start, parts[1:])
        if isinstance(first, PGraph):
            start = ctx.doc_value()
            return get_path(ctx, start, parts)
        if isinstance(first, PField):
            if ctx.doc is not None:
                return get_path(ctx, ctx.doc_value(), parts)
            # no doc: a bare identifier denotes a table reference
            if len(parts) == 1:
                from .value import Table

                return Table(first.name)
            return NONE
        return get_path(ctx, ctx.doc_value(), parts)

    def writeable(self):
        return any(
            isinstance(p, PStart) and p.expr.writeable() for p in self.parts
        )

    def simple_name(self) -> Optional[str]:
        """If this is a single plain field (`name`), return it."""
        if len(self.parts) == 1 and isinstance(self.parts[0], PField):
            return self.parts[0].name
        return None

    def field_path(self) -> Optional[List[str]]:
        """If purely nested fields (`a.b.c`), return the name list."""
        out = []
        for p in self.parts:
            if isinstance(p, PField):
                out.append(p.name)
            else:
                return None
        return out or None

    def __repr__(self):
        out = []
        for i, p in enumerate(self.parts):
            if i == 0 and isinstance(p, PField):
                out.append(escape_ident(p.name))
            else:
                out.append(repr(p))
        return "".join(out)

    def __eq__(self, other):
        return isinstance(other, Idiom) and repr(self) == repr(other)

    def __hash__(self):
        return hash(repr(self))


# ------------------------------------------------------------------- get
def _fetch_record(ctx, thing: Thing):
    ns, db = ctx.ns_db()
    doc = ctx.txn().get_record(ns, db, thing.tb, thing.id)
    return doc if doc is not None else NONE


def get_path(ctx, value, parts: List[Part]):
    """Apply path parts to a value, fetching records / walking edges."""
    if not parts:
        return value
    p, rest = parts[0], parts[1:]

    # record pointer: fetch before applying a field-ish part
    if isinstance(value, Thing) and not isinstance(p, (POptional,)):
        if isinstance(p, PGraph):
            return _graph_part(ctx, [value], p, rest)
        if isinstance(p, PMethod):
            # record methods dispatch on the POINTER, not the fetched doc
            # (reference record-type method table: exists/id/tb/table)
            return _method_call(ctx, value, p, rest)
        value = _fetch_record(ctx, value)

    if isinstance(p, PStart):
        return get_path(ctx, p.expr.compute(ctx), rest)

    if isinstance(p, POptional):
        if is_nullish(value):
            return NONE
        return get_path(ctx, value, rest)

    if isinstance(p, PGraph):
        things = value if isinstance(value, list) else [value]
        things = [_as_thing(t) for t in things]
        things = [t for t in things if t is not None]
        return _graph_part(ctx, things, p, rest)

    if isinstance(p, PRecurse):
        return _recurse_part(ctx, value, p, rest)

    if isinstance(value, list):
        if isinstance(p, PIndex):
            v = value[p.i] if -len(value) <= p.i < len(value) else NONE
            return get_path(ctx, v, rest)
        if isinstance(p, PLast):
            return get_path(ctx, value[-1] if value else NONE, rest)
        if isinstance(p, PAll):
            return [get_path(ctx, v, rest) for v in value]
        if isinstance(p, PWhere):
            kept = []
            for v in value:
                dv = _fetch_record(ctx, v) if isinstance(v, Thing) else v
                with ctx.with_doc_value(dv, rid=v if isinstance(v, Thing) else None) as c:
                    if truthy(p.cond.compute(c)):
                        kept.append(v)
            return get_path(ctx, kept, rest)
        if isinstance(p, PValue):
            idx = p.expr.compute(ctx)
            if isinstance(idx, int) and not isinstance(idx, bool):
                v = value[idx] if -len(value) <= idx < len(value) else NONE
                return get_path(ctx, v, rest)
            if isinstance(idx, Range):
                lo = idx.beg if not is_none(idx.beg) else 0
                hi = idx.end if not is_none(idx.end) else len(value)
                if not idx.beg_incl:
                    lo += 1
                if idx.end_incl:
                    hi += 1
                return get_path(ctx, value[int(lo) : int(hi)], rest)
            return get_path(ctx, NONE, rest)
        if isinstance(p, PFlatten):
            flat = []
            for v in value:
                if isinstance(v, list):
                    flat.extend(v)
                else:
                    flat.append(v)
            return get_path(ctx, flat, rest)
        if isinstance(p, PMethod):
            return _method_call(ctx, value, p, rest)
        # field access distributes over arrays
        out = [get_path(ctx, v, [p]) for v in value]
        return get_path(ctx, out, rest)

    if isinstance(value, dict):
        if isinstance(p, PField):
            return get_path(ctx, value.get(p.name, NONE), rest)
        if isinstance(p, PAll):
            return get_path(ctx, value, rest) if not rest else {
                k: get_path(ctx, v, rest) for k, v in value.items()
            }
        if isinstance(p, PValue):
            k = p.expr.compute(ctx)
            if isinstance(k, str):
                return get_path(ctx, value.get(k, NONE), rest)
            return get_path(ctx, NONE, rest)
        if isinstance(p, PDestructure):
            out = {}
            for name, sub in p.fields:
                if sub is None:
                    out[name] = value.get(name, NONE)
                else:
                    out[name] = get_path(ctx, value, sub)
            return get_path(ctx, out, rest)
        if isinstance(p, PMethod):
            return _method_call(ctx, value, p, rest)
        if isinstance(p, PWhere):
            with ctx.with_doc_value(value) as c:
                ok = truthy(p.cond.compute(c))
            return get_path(ctx, value if ok else NONE, rest)
        return get_path(ctx, NONE, rest)

    if isinstance(p, PMethod):
        return _method_call(ctx, value, p, rest)

    if is_nullish(value):
        return NONE

    # scalar with remaining non-applicable parts
    return NONE


def _method_call(ctx, value, p: PMethod, rest):
    """`.method(args)`: closure field first, else builtin whose first arg is
    the receiver (reference: "value methods")."""
    from surrealdb_tpu import fnc
    from surrealdb_tpu.fnc.custom import run_closure
    from .value import Closure as ClosureV

    if isinstance(value, dict) and isinstance(value.get(p.name), ClosureV):
        args = [a.compute(ctx) for a in p.args]
        return get_path(ctx, run_closure(ctx, value[p.name], args), rest)
    args = [a.compute(ctx) for a in p.args]
    out = fnc.run_method(ctx, p.name, value, args)
    return get_path(ctx, out, rest)


def _as_thing(v) -> Optional[Thing]:
    """A record pointer: a Thing itself or a fetched document's id."""
    if isinstance(v, Thing):
        return v
    if isinstance(v, dict) and isinstance(v.get("id"), Thing):
        return v["id"]
    return None


# ------------------------------------------------------------------- graph
def graph_hop(ctx, things: List[Thing], dir_: str, what: List[str]) -> List[Thing]:
    """One edge hop: scan graph-pointer keys for each source id.

    Reference behavior: processor.rs:610-701 collect_edges. The TPU CSR path
    (idx/graph.py) accelerates multi-hop frontiers; this is the exact KV walk.
    """
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    dirs = {"out": [keys.DIR_OUT], "in": [keys.DIR_IN], "both": [keys.DIR_IN, keys.DIR_OUT]}[
        dir_
    ]
    out: List[Thing] = []
    for t in things:
        for d in dirs:
            if what:
                for ft in what:
                    pre = keys.graph_prefix(ns, db, t.tb, t.id, d, ft)
                    for k in txn.keys(pre, _prefix_end(pre)):
                        _, _, _, fk = keys.decode_graph(k, ns, db, t.tb)
                        out.append(fk)
            else:
                pre = keys.graph_prefix(ns, db, t.tb, t.id, d)
                for k in txn.keys(pre, _prefix_end(pre)):
                    _, _, _, fk = keys.decode_graph(k, ns, db, t.tb)
                    out.append(fk)
    return out


def _prefix_end(p: bytes) -> bytes:
    from surrealdb_tpu.key.encode import prefix_end

    return prefix_end(p)


def _chain_over_record(ctx, expr):
    """(the current record's id, the parts) where `expr`, the one argument
    of an aggregate (`count`, `array::distinct`), is an idiom of graph
    parts alone over the current record; None for anything else."""
    if not is_graph_chain(expr):
        return None
    doc = ctx.doc
    rid = doc.rid if doc is not None else None
    return (rid, expr.parts) if isinstance(rid, Thing) else None


def _chain_rides(ctx, parts) -> bool:
    """Is the chain one the mirrors can serve an aggregate of: every part
    eligible (_mirror_eligible), with no WHERE but on its final part, which
    then names one node table that this transaction has not written (the
    column mirror refuses such a reader: ColumnMirrors.serveable)? Asked of
    the statement as it runs (this transaction's uncommitted writes), so
    of every expression, also of one that the statement's memo answers."""
    last = parts[-1]
    if last.cond is not None and len(last.what) != 1:
        return False
    if not (all(_mirror_eligible(ctx, p) for p in parts[:-1]) and _mirror_eligible(ctx, last, cond_ok=True)):
        return False
    return last.cond is None or (*ctx.ns_db(), last.what[0]) not in getattr(ctx.txn(), "touched_tables", ())


def _riding_where(ctx, parts):
    """The compiled predicate (ops/predicates.py) of a chain whose final
    part alone has a WHERE, names one node table there and lowers onto
    that table's column mirror, every part eligible for the mirrors
    (_chain_rides): what can ride an aggregate of the chain as a mask.
    None where the chain or the predicate is not of that kind."""
    if parts[-1].cond is None or not _chain_rides(ctx, parts):
        return None
    from surrealdb_tpu.ops.predicates import compile_where

    return compile_where(ctx, parts[-1].cond)


def graph_chain_count(ctx, expr) -> "int | None":
    """count(->a->b->c) fast path: when the argument is a graph-chain idiom
    over the current record whose parts name their tables, sum the path
    counts on the CSR frontier without expanding (idx/graph_csr.py
    chain_count). Eligible: a chain with no WHERE at all, or one whose only
    WHERE sits on its final part, names one node table there and lowers
    onto that table's column mirror (ops/predicates.py compile_where:
    comparisons of the node's own fields against constants or bound
    parameters, AND / OR / NOT); the predicate then rides the count as a
    mask. Returns None when ineligible — the caller falls back to normal
    evaluation, so this is purely an execution strategy, never a semantics
    change. A chain with a WHERE that cannot ride (on an edge part or a
    middle part, not lowerable, several tables in the last part,
    uncommitted edge writes, a column mirror this reader may not use) is
    walked here as the caller would walk it, so that its `graph_prepare`
    span can say `filter=host`."""
    chain = _chain_over_record(ctx, expr)
    if chain is None:
        return None
    rid, parts = chain
    if all(p.cond is None for p in parts):
        for p in parts:
            if not _mirror_eligible(ctx, p):
                return None
        # no exception guard: deadline/internal errors must propagate, not
        # silently re-run the whole traversal on the slow path
        n = ctx.ds().graph_mirrors.chain_count(ctx, [rid], list(parts))
        ctx.executor.op_end = time.perf_counter()
        return n
    t_enter = time.perf_counter()
    n = _filtered_chain_count(ctx, rid, parts)
    if n is None:
        from surrealdb_tpu import fnc

        n = fnc.run(ctx, "count", [expr.compute(ctx)], exprs=[expr])
        mirrors = getattr(ctx.ds(), "graph_mirrors", None)
        if mirrors is not None:
            mirrors.count_walked(t_enter)
    ctx.executor.op_end = time.perf_counter()
    return n


def _filtered_chain_count(ctx, rid: Thing, parts) -> "int | None":
    """The count of a chain whose final part alone has a WHERE, off the
    mirrors; None where the chain or the predicate is not of that kind."""
    where = _riding_where(ctx, parts)
    if where is None:
        return None
    return ctx.ds().graph_mirrors.chain_count(ctx, [rid], list(parts), where=where)


def graph_chain_distinct(ctx, expr, deepest) -> "list | None":
    """array::distinct(->a->b->c) fast path: the set form of
    graph_chain_count, for the same chains by the same test (no WHERE, or
    one on the final node part that rides as a mask), served off the
    mirrors as sets hop by hop (idx/graph_csr.py chain_distinct) and never
    as the multiset chain() lays out for `array::distinct` to shrink.
    `deepest` is what the parser found for this call
    (mark_chain_families): the longest chain among the statement's
    `array::distinct` calls that this one is a prefix of. One run of it
    serves them all, and the statement's memo (Executor.reach_memo) stands
    IN FRONT of the preparation: under (that idiom node, the start record,
    the WHERE's constants as bound now: ops/predicates.py bound_constants)
    it keeps what the family's first expression ran, and a sibling reads
    its ring there without compiling a predicate or looking an operator
    up. The constants are in the key because the family is the parser's,
    of the text, and the memo lives a top-level statement: a cached
    template's literals are slots, and two that were one text when parsed
    may be bound apart; a FOR, a LET in a block or a function's argument
    rebinds a parameter between two evaluations of one node. Such an
    expression finds nothing under its key and runs as one alone does,
    under its own predicate. So does one whose ring the family's program
    did not keep. What the statement's own transaction wrote since is
    _chain_rides' to see, before the memo is asked. None when ineligible,
    and the caller evaluates as it always did; a chain with a WHERE that
    cannot ride is evaluated here, as the caller would, so that its
    `graph_prepare` span can say `filter=host`."""
    chain = _chain_over_record(ctx, expr)
    if chain is None:
        return None
    rid, parts = chain
    rides = _chain_rides(ctx, parts)
    if not rides and all(p.cond is None for p in parts):
        return None
    from surrealdb_tpu.ops.predicates import bound_constants, compile_where

    t_enter = time.perf_counter()
    mirrors = getattr(ctx.ds(), "graph_mirrors", None)
    cond = parts[-1].cond
    found = None
    if rides:
        bound = bound_constants(ctx, cond)
        memo, key = ctx.executor.reach_memo, (id(deepest), rid.tb, repr(rid.id), bound)  # person:1 is not person:1.0
        family = memo.get(key)
        if family is not None:
            found = mirrors.ring(ctx, family, parts, t_enter)
        if found is None:
            where = None if cond is None else compile_where(ctx, cond)
            if cond is None or where is not None:
                # the family's chain where nobody ran it yet and it is this
                # chain as bound; else this chain alone, and nobody reads that
                far = deepest.parts if family is None and _rides_alike(ctx, deepest.parts, parts, bound) else parts
                found = mirrors.chain_distinct(
                    ctx, rid, parts, where=where, deepest=far, keep=(memo, key) if far is deepest.parts else None
                )
    if found is None:
        from surrealdb_tpu import fnc

        found = fnc.run(ctx, "array::distinct", [expr.compute(ctx)], exprs=[expr])
        if mirrors is not None:
            mirrors.reach_walked(t_enter, len(parts) // 2, len(found))
    return found


def fill_reach_groups(ctx, calls: Sequence, rows: Sequence) -> None:
    """A SELECT's collected `rows` ((rid, doc, ir), two or more:
    dbs/iterator.py defers their projection) and the `array::distinct(<graph
    chain>)` calls of its field list (`calls`: the parser's note,
    SelectStatement.reach_calls): for each chain family among them (the
    deepest chain, as mark_chain_families found it), ONE test that the
    chain rides (_chain_rides), ONE compiled predicate, and one run of the
    chain for every row's record together (idx/graph_csr.py::reach_group:
    the rows' frontiers in one call to the dispatch queue). Each row's
    rings land in the statement's memo under that row's own key, as
    graph_chain_distinct keeps one row's: the projection's expressions then
    read their rings there (`memo=hit`), and nothing of the per-expression
    logic changes. A family that cannot ride as a whole (a WHERE on a
    middle part, a predicate that does not lower, this transaction's own
    edge writes, TPU_DISABLE), a row that is no record of the first row's
    table or a start the group could not serve is not filled, and
    evaluates as it does alone."""
    mirrors = getattr(ctx.ds(), "graph_mirrors", None)
    families = list({id(c.reach): c.reach for c in calls}.values())
    rid0, doc0, ir0 = rows[0]
    if mirrors is None or not isinstance(rid0, Thing):
        return
    from surrealdb_tpu.ops.predicates import bound_constants, compile_where

    memo = ctx.executor.reach_memo
    for deepest in families:
        parts = deepest.parts
        if not _chain_rides(ctx, parts):
            continue
        cond, where = parts[-1].cond, None
        with ctx.with_doc_value(doc0, rid=rid0, ir=ir0) as c:
            bound = bound_constants(c, cond)
            if cond is not None:
                where = compile_where(c, cond)
                if where is None:
                    continue
        todo = {}
        for rid, _, _ in rows:
            if isinstance(rid, Thing) and rid.tb == rid0.tb:
                key = (id(deepest), rid.tb, repr(rid.id), bound)
                if key not in memo:
                    todo[key] = rid
        if len(todo) < 2:
            continue
        gots = mirrors.reach_group(ctx, list(todo.values()), parts, where=where, families=len(families))
        for key, got in zip(todo, gots or ()):
            if got is not None:
                memo[key] = got


def _rides_alike(ctx, far: List[Part], parts: List[Part], bound: tuple) -> bool:
    """Can the chain `far`, of which `parts` was a prefix when the
    statement was parsed (chain_prefix: one WHERE text), stand in for it
    as the statement is bound now: eligible for the mirrors, and its WHERE's
    constants bound as `bound`, which are those of `parts`' own?"""
    if far is parts:
        return True
    from surrealdb_tpu.ops.predicates import bound_constants

    return _chain_rides(ctx, far) and bound_constants(ctx, far[-1].cond) == bound


def chain_prefix(a: List[Part], b: List[Part]) -> bool:
    """Is the graph chain `a` a prefix of the graph chain `b` under one
    predicate: the same direction and tables part for part, no WHERE
    before either's final part, and on the final parts the same WHERE text
    (or none on both)? The set `a` reaches is then the set `b` reaches
    after len(a) parts."""
    if len(a) > len(b) or repr(a[-1].cond) != repr(b[-1].cond):
        return False
    if any(p.cond is not None for p in a[:-1] + b[:-1]):
        return False
    return all(p.dir == q.dir and p.what == q.what for p, q in zip(a, b))


def mark_chain_families(calls: List) -> None:
    """The parser's note on each `array::distinct(<graph chain>)` call of
    one statement (ast.FunctionCall.reach): the argument of the call with
    the longest chain that this call's chain is a prefix of (chain_prefix;
    its own where there is no other). Kept with the statement's AST, so
    with the plan cache's template of it."""
    for call in calls:
        mine = call.args[0]
        family = [c.args[0] for c in calls if chain_prefix(mine.parts, c.args[0].parts)]
        call.reach = max(family, key=lambda idiom: len(idiom.parts), default=mine)


def is_graph_chain(expr) -> bool:
    return isinstance(expr, Idiom) and bool(expr.parts) and all(isinstance(p, PGraph) for p in expr.parts)


def _mirror_eligible(ctx, p: PGraph, cond_ok: bool = False) -> bool:
    """A hop can ride the CSR mirrors when its edge tables are named, it has
    no per-record WHERE (`cond_ok`: but for a count chain's final part,
    whose WHERE graph_chain_count takes to the column mirror itself), and
    this transaction has no uncommitted edge writes (those are only visible
    to the exact KV walk)."""
    if (p.cond is not None and not cond_ok) or not p.what:
        return False
    try:
        return ctx.ds() is not None and not ctx.txn().graph_deltas
    except Exception:
        return False


def _graph_part(ctx, things: List[Thing], p: PGraph, rest: List[Part]):
    # batched frontier path: a maximal run of eligible graph parts becomes a
    # chain of CSR gather hops (device above TPU_GRAPH_ONDEVICE_THRESHOLD)
    # instead of per-record `~` prefix scans (reference processor.rs:610-701)
    if things and _mirror_eligible(ctx, p):
        chain = [p]
        i = 0
        while (
            i < len(rest)
            and isinstance(rest[i], PGraph)
            and _mirror_eligible(ctx, rest[i])
        ):
            chain.append(rest[i])
            i += 1
        found = ctx.ds().graph_mirrors.chain(ctx, things, chain)
        return get_path(ctx, found, rest[i:])
    found = graph_hop(ctx, things, p.dir, p.what)
    if p.cond is not None:
        kept = []
        for t in found:
            doc = _fetch_record(ctx, t)
            with ctx.with_doc_value(doc, rid=t) as c:
                if truthy(p.cond.compute(c)):
                    kept.append(t)
        found = kept
    # no dedup: the reference flattens hop results without deduplication
    # (sql/value/get.rs:404-446), so parallel edges / converging paths
    # yield duplicate records — multiplicity is part of the result
    return get_path(ctx, found, rest)


def _recurse_part(ctx, value, p: PRecurse, rest: List[Part]):
    from surrealdb_tpu import cnf

    max_depth = p.max if p.max is not None else cnf.IDIOM_RECURSION_LIMIT
    if max_depth > cnf.IDIOM_RECURSION_LIMIT:
        raise TypeError_("Recursion depth exceeds the allowed limit")
    cur = value
    depth = 0
    while depth < max_depth:
        nxt = get_path(ctx, cur, p.parts)
        if isinstance(nxt, list) and not nxt:
            break
        if is_nullish(nxt):
            break
        cur = nxt
        depth += 1
        if depth >= p.min and p.max is None:
            # unbounded: iterate to fixpoint-ish; stop when result repeats
            continue
    if depth < p.min:
        return NONE
    return get_path(ctx, cur, rest)


# ------------------------------------------------------------------- set/del
def set_path(ctx, value, parts: List[Part], new) -> Any:
    """Set a nested path inside a document value (mutates dicts/lists)."""
    if not parts:
        return new
    p, rest = parts[0], parts[1:]
    if isinstance(p, PField):
        if isinstance(value, dict):
            if not rest:
                value[p.name] = new
            else:
                cur = value.get(p.name, NONE)
                if is_nullish(cur) or not isinstance(cur, (dict, list)):
                    cur = {} if not isinstance(
                        rest[0], (PIndex, PAll, PLast)
                    ) else []
                    value[p.name] = cur
                set_path(ctx, cur, rest, new)
        elif isinstance(value, list):
            for item in value:
                set_path(ctx, item, parts, new)
        return value
    if isinstance(p, PIndex):
        if isinstance(value, list) and -len(value) <= p.i < len(value):
            if not rest:
                value[p.i] = new
            else:
                set_path(ctx, value[p.i], rest, new)
        return value
    if isinstance(p, PLast):
        if isinstance(value, list) and value:
            if not rest:
                value[-1] = new
            else:
                set_path(ctx, value[-1], rest, new)
        return value
    if isinstance(p, PAll):
        if isinstance(value, list):
            if not rest:
                value[:] = [new for _ in value]
            else:
                for item in value:
                    set_path(ctx, item, rest, new)
        elif isinstance(value, dict):
            if not rest:
                for k in value:
                    value[k] = new
            else:
                for k in value:
                    set_path(ctx, value[k], rest, new)
        return value
    if isinstance(p, PWhere):
        if isinstance(value, list):
            for item in value:
                dv = item
                with ctx.with_doc_value(dv) as c:
                    if truthy(p.cond.compute(c)):
                        set_path(ctx, item, rest, new) if rest else None
        return value
    if isinstance(p, PValue):
        k = p.expr.compute(ctx)
        if isinstance(value, dict) and isinstance(k, str):
            if not rest:
                value[k] = new
            else:
                cur = value.get(k)
                if not isinstance(cur, (dict, list)):
                    cur = {}
                    value[k] = cur
                set_path(ctx, cur, rest, new)
        elif isinstance(value, list) and isinstance(k, int):
            if -len(value) <= k < len(value):
                if not rest:
                    value[k] = new
                else:
                    set_path(ctx, value[k], rest, new)
        return value
    return value


def del_path(ctx, value, parts: List[Part]) -> Any:
    if not parts:
        return value
    p, rest = parts[0], parts[1:]
    if isinstance(p, PField):
        if isinstance(value, dict):
            if not rest:
                value.pop(p.name, None)
            elif p.name in value:
                del_path(ctx, value[p.name], rest)
        elif isinstance(value, list):
            for item in value:
                del_path(ctx, item, parts)
        return value
    if isinstance(p, PIndex):
        if isinstance(value, list) and -len(value) <= p.i < len(value):
            if not rest:
                del value[p.i]
            else:
                del_path(ctx, value[p.i], rest)
        return value
    if isinstance(p, PAll):
        if isinstance(value, list):
            if not rest:
                value.clear()
            else:
                for item in value:
                    del_path(ctx, item, rest)
        return value
    if isinstance(p, PWhere):
        if isinstance(value, list):
            if not rest:
                keep = []
                for item in value:
                    with ctx.with_doc_value(item) as c:
                        if not truthy(p.cond.compute(c)):
                            keep.append(item)
                value[:] = keep
            else:
                for item in value:
                    with ctx.with_doc_value(item) as c:
                        if truthy(p.cond.compute(c)):
                            del_path(ctx, item, rest)
        return value
    if isinstance(p, PValue):
        k = p.expr.compute(ctx)
        if isinstance(value, dict) and isinstance(k, str):
            if not rest:
                value.pop(k, None)
            elif k in value:
                del_path(ctx, value[k], rest)
        return value
    return value
