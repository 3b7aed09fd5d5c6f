"""Fingerprint-keyed plan & pipeline cache: serve hot statement shapes
without re-parsing or re-planning.

The workload statistics plane (stats.py) proved that production traffic
collapses onto a small set of statement SHAPES — the PR 15 fingerprint.
Every execution still paid the full cold ladder: parse, plan probe
(`txn.all_tb_indexes`), pipeline lowering (`ops/pipeline.analyze_select`),
predicate compile (`ops/predicates.compile_where`). This module caches all
of it per fingerprint and serves hot shapes from memory:

- **Template AST.** The first parses of a shape install the parsed Query
  as a shared template. Literal slots are parameterized (`ast.SlotLiteral`)
  so `WHERE age > 30` and `WHERE age > 40` — and the `$param` spelling of
  the same shape — share one entry; the active execution's values ride the
  per-query Executor (`executor.slot_values`), never the shared nodes.
  An inline record id is such a slot too (`ast.SlotThing`) where its id
  part is one number, string or UUID token after the table's name:
  `FROM person:42` and `FROM person:43` are one template. Every other
  spelling of an id (an identifier, `likes:8abc2`, `t:8e2`, a range, an
  array, object or expression id, an id whose value the statement repeats)
  stays a fixed token, served for its exact text only: `_parameterize`.
- **Dispatch skeleton.** Which `dbs/stmt_exec.select_compute` front
  resolved the statement (ml / count / pipeline / plan), so warm serves
  skip the fronts that declined cold.
- **Pipeline lowering.** The resolved `ops/pipeline.Lowering` — grouped
  shape or order specs, projection, and the compiled `ops/predicates.py`
  mask *program*. Mask content still binds per execution: the compiled
  predicate is `rebind()`-ed against the live context on every serve.
- **Planner schema prefetch.** The `all_tb_indexes` probe result per
  (ns, db, tb), so `idx/planner._build_index_plan` skips its per-execution
  KV scan.

Correctness is validation-on-serve, NEVER TTL:

- **Binding is verified, not assumed.** A new text that lex-matches a
  parameterized variant is parsed ONCE and structurally compared against
  the bound template (`_ast_equal`). Only after `_VERIFY_TRUST` distinct
  texts verify byte-identically does the variant serve on lex alone; a
  single mismatch demotes it to exact-digest serving forever.
- **Schema/index generation.** Routes record a per-(ns, db) generation.
  DDL (`DEFINE`/`REMOVE`/`ALTER`/`REBUILD`, and the async index builder's
  ready flip) brackets itself with `ddl_begin`/`ddl_end`: the begin bump
  invalidates every pre-DDL artifact, installs are refused while a DDL is
  in flight, and the end bump invalidates anything raced in between — no
  window in which a plan built on the old schema can be served against
  the new one.
- **Tenant/session scope.** Route artifacts are keyed by
  (ns, db, auth level, roles, access, record id): a cached plan never
  leaks across tenants or privilege levels. The template AST itself is
  scope-free (it is just the parse).
- **Cluster epoch.** Routes record the membership epoch seen at install;
  `note_epoch` invalidates them all when the ring changes.
- **Mirror serve state.** A cached pipeline serve that the mirror
  declines drops the route (cause `mirror`) and falls back to the cold
  ladder, which re-resolves and re-installs.
- **Plan-mix flips.** A PR 15 plan-flip (`stats.record`) evicts the
  flipped fingerprint's whole entry (cause `flip`) — visible as a
  `plan_cache.evict` event and a `plan_cache_invalidations` count.
- **Periodic revalidation.** Every `_REVALIDATE_EVERY` serves a route
  declines once so the cold ladder re-derives it — insurance against
  decisions pinned forever (a cached row route never re-attempting a
  newly serveable mirror).

Every mutation goes through this class — the single write door graftlint
GL015 enforces statically. Knobs: `SURREAL_PLAN_CACHE` (on/off),
`SURREAL_PLAN_CACHE_CAP` (entries), `SURREAL_PLAN_CACHE_MIN_HITS`
(observations before a shape is installed).

Lock discipline: `plan_cache.store` is a leaf-style observability lock
(locks.HIERARCHY level 85). Telemetry counters and `plan_cache.evict`
events are emitted AFTER release, mirroring stats.py.
"""

from __future__ import annotations

import hashlib
import time
import weakref
from collections import Counter, OrderedDict, deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from surrealdb_tpu.utils import locks as _locks

_DIGEST_CAP = 32  # distinct literal combinations remembered per variant
_VARIANT_CAP = 4  # arity/spelling variants kept per fingerprint entry
_SCOPE_CAP = 8  # tenant/session scopes with routes per variant
_VERIFY_TRUST = 4  # verified lex-serves before a variant skips the parse
_REVALIDATE_EVERY = 64  # serves between forced cold re-resolutions
_EVLOG_CAP = 64  # recent evictions kept for snapshot()'s recent_evictions


class Served(NamedTuple):
    """One warm AST serve: the shared template Query plus this
    execution's slot bindings (None when the variant is unparameterized),
    and how it was found: `digest` (this exact text before, nothing
    lexed) or `lexed` (a new text bound into a variant's signature)."""

    query: Any
    slot_values: Optional[Tuple[Any, ...]]
    fp: str
    kind: str


class _Route:
    """One tenant scope's resolved dispatch for a template statement."""

    __slots__ = ("front", "lowering", "gen", "epoch", "serves", "installed")

    def __init__(self, front: str, gen: Tuple, epoch: Any):
        self.front = front
        self.lowering = None  # ops/pipeline.Lowering for front == "pipeline"
        self.gen = gen  # (ns, db, generation) captured at statement start
        self.epoch = epoch
        self.serves = 0
        self.installed = time.time()


class _Variant:
    """One spelling of a fingerprint: a shared template AST plus the
    token signature that decides whether a new text can bind into it."""

    __slots__ = (
        "query", "stmt", "kinds", "fixed", "slot_idx", "id_types", "defaults",
        "digests", "routes", "parameterized", "trust", "text",
    )

    def __init__(self, query, kinds, fixed, slot_idx, id_types, defaults, text):
        self.query = query
        self.stmt = query.statements[0]
        self.kinds = kinds  # signature token kinds, source order
        self.fixed = fixed  # ((token_idx, value), ...) must match verbatim
        self.slot_idx = slot_idx  # token indices bound to SlotLiteral slots
        # ((token_idx, type), ...) of the slots that are record ids: the
        # parser builds `tb:5` (int) and `tb:5f` (the string "5f") from one
        # token kind, so such a slot binds a value of the template's type only
        self.id_types = id_types
        self.defaults = defaults  # the installing text's own slot values
        self.digests: "OrderedDict[str, Optional[Tuple]]" = OrderedDict()
        self.routes: "OrderedDict[Tuple, _Route]" = OrderedDict()
        self.parameterized = bool(slot_idx)
        self.trust = 0  # verified lex-serves; >= _VERIFY_TRUST skips verify
        self.text = text  # first-seen spelling (views/debug only)


class _Entry:
    """One fingerprint's cached variants and serve counters."""

    __slots__ = ("fp", "variants", "hits", "route_hits", "misses",
                 "invalidations", "churn", "bound", "refused", "installed_ts")

    def __init__(self, fp: str):
        self.fp = fp
        self.variants: List[_Variant] = []
        self.hits = 0
        self.route_hits = 0
        self.misses = 0
        self.invalidations = 0
        self.churn = 0  # variant capacity evictions (thrash guard)
        self.bound = False  # some spelling of the shape has had a slot
        self.refused = 0  # observes turned away unexamined by the guard
        self.installed_ts = time.time()


# statements whose ASTs are safe and worth sharing: no DDL (those bump
# generations instead), no LIVE/KILL (a live query retains its AST past
# the execution, where slot bindings would no longer ride the executor),
# no transaction control, no EXPLAIN (stmt_exec mutate-restores it)
def _cacheable(stm) -> bool:
    from surrealdb_tpu.sql import statements as S

    if not isinstance(
        stm,
        (
            S.SelectStatement, S.CreateStatement, S.UpdateStatement,
            S.DeleteStatement, S.InsertStatement, S.RelateStatement,
            S.ReturnStatement,
        ),
    ):
        return False
    if isinstance(stm, S.SelectStatement) and (
        stm.explain or stm.explain_full or stm.explain_analyze
    ):
        return False
    return True


def _stmt_key(text: str) -> str:
    """Canonical single-statement text: what the parser records as the
    statement's source (`Query.sources`) and what stats.fingerprint keys
    on — leading/trailing separators stripped so `SELECT 1` and
    `SELECT 1;` share the entry the flip hook will evict."""
    return text.strip().strip(";").strip()


def _digest(key: str) -> str:
    return hashlib.blake2b(key.encode("utf-8"), digest_size=8).hexdigest()


def _fixed_eq(a: Any, b: Any) -> bool:
    """Strict signature equality: same concrete type AND equal value
    (int 5 never matches float 5.0 — binding the wrong numeric flavor
    changes results). Regex-ish values compare by pattern (fresh lex
    runs produce distinct objects)."""
    if a is b:
        return True
    if type(a) is not type(b):
        return False
    pa = getattr(a, "pattern", None)
    if pa is not None:
        return pa == getattr(b, "pattern", None)
    try:
        return bool(a == b)
    except Exception:
        return False


# ------------------------------------------------------------------ AST walk
def _is_sql_node(o: Any) -> bool:
    return type(o).__module__.startswith("surrealdb_tpu.sql")


def _slot_names(o: Any) -> List[str]:
    names: List[str] = []
    for klass in type(o).__mro__:
        names.extend(getattr(klass, "__slots__", ()))
    return names


def _collect_literal_sites(root) -> List[Tuple[Any, Any, Any]]:
    """Every exact-type ast.Literal reachable from `root`, as
    (container, key, node) so the node can be swapped for a SlotLiteral.
    Literals inside tuples/sets are unreplaceable and not collected —
    their tokens stay fixed in the signature, which is always sound. So
    do those of a projection that is named by its own text: an unaliased
    `SELECT 5, person:1.name` keys its output by repr(expr)
    (dbs/iterator.field_display_name), which on a shared template would
    print the first-seen literal; a bare function call is named by the
    function alone and SELECT VALUE names nothing."""
    from surrealdb_tpu.sql import ast as A
    from surrealdb_tpu.sql import statements as S

    sites: List[Tuple[Any, Any, Any]] = []
    seen: set = set()

    def consider(container, key, v) -> bool:
        if type(v) is A.Literal:
            sites.append((container, key, v))
            return True
        return False

    def walk(o) -> None:
        oid = id(o)
        if oid in seen:
            return
        seen.add(oid)
        if isinstance(o, list):
            for i, v in enumerate(o):
                if not consider(o, i, v):
                    walk(v)
        elif isinstance(o, dict):
            for k, v in list(o.items()):
                if not consider(o, k, v):
                    walk(v)
        elif isinstance(o, (tuple, set, frozenset)):
            for v in o:
                walk(v)
        elif _is_sql_node(o):
            if type(o) is S.SelectStatement:
                # the parser's notes repeat nodes of the field list: their
                # literals are sites where the walk meets them THERE, or not
                # at all (the unaliased projections below)
                seen.update(id(note) for note in (o.ml_calls, o.reach_calls) if note)
            if type(o) is S.Output or (
                type(o) is S.SelectStatement and not o.value_mode
            ):
                seen.update(
                    id(f) for f in o.fields or ()
                    if f.alias is None and f.expr is not None
                    and not isinstance(f.expr, A.FunctionCall)
                )
            for name in _slot_names(o):
                try:
                    v = getattr(o, name)
                except AttributeError:
                    continue
                if not consider(o, name, v):
                    walk(v)

    walk(root)
    return sites


_UNSET = object()


def _ast_equal(tmpl, fresh, slot_values: Tuple[Any, ...]) -> bool:
    """Structural equality of the bound template against a fresh parse —
    the serve-time proof that slot binding reproduces exactly what the
    parser would have built for the new text. A `__slots__` name the
    parser set on neither side (PGraph.expr_fields) is equal; one set on
    one side only is not."""
    from surrealdb_tpu.sql import ast as A
    from surrealdb_tpu.sql.value import Thing

    def eq(a, b) -> bool:
        if isinstance(a, A.SlotLiteral):
            if type(b) is not A.Literal or a.slot >= len(slot_values):
                return False
            bound = slot_values[a.slot]
            if isinstance(a, A.SlotThing):
                return (
                    type(b.value) is Thing
                    and b.value.tb == a.value.tb
                    and _fixed_eq(bound, b.value.id)
                )
            return _fixed_eq(bound, b.value)
        if type(a) is not type(b):
            return False
        if isinstance(a, list) or isinstance(a, tuple):
            return len(a) == len(b) and all(eq(x, y) for x, y in zip(a, b))
        if isinstance(a, dict):
            if a.keys() != b.keys():
                return False
            return all(eq(v, b[k]) for k, v in a.items())
        if _is_sql_node(a):
            for name in _slot_names(a):
                va, vb = getattr(a, name, _UNSET), getattr(b, name, _UNSET)
                if va is _UNSET or vb is _UNSET:
                    if va is not vb:
                        return False
                elif not eq(va, vb):
                    return False
            return True
        return _fixed_eq(a, b)

    return eq(tmpl, fresh)


def _carries(node_value: Any, v: Any, tb: Any) -> bool:
    """Does a bindable token's value `v`, whose signature neighbour to the
    left is the identifier `tb` (None when it is no identifier), carry
    this Literal's value? A plain literal: the token's value is the
    node's. A record id `tb:v`: the node holds Thing(tb, v), and only for
    an id the parser takes from the token as it is (int, string, UUID)."""
    from surrealdb_tpu.sql.value import Thing, Uuid

    if type(node_value) is Thing:
        return (
            tb == node_value.tb
            and type(v) in (int, str, Uuid)
            and _fixed_eq(node_value.id, v)
        )
    return _fixed_eq(node_value, v)


def _parameterize(text: str, query) -> Optional[_Variant]:
    """Build a variant for `query` (parsed from `text`): lex the
    signature tokens, match bindable token values 1:1 against replaceable
    Literal nodes, swap matches for SlotLiterals.

    A record id binds too (`ast.SlotThing`, the table stays a fixed
    token) where its id part is ONE bindable token that directly follows
    the table's identifier: `person:42`, `person:'a b'`, `person:u'..'`.
    Every other spelling of an id stays fixed, each its own exact text:
    an identifier (`person:alice`, backticks, angle brackets: not a
    bindable kind, and the fingerprint keeps it), a digit-led id that
    lexes as several tokens (`likes:8abc2`, and any number with an
    identifier glued to it: signature kind GLUED), a number the parser
    reads back as text (`t:8e2`, `t:1h`), ranges, array and object ids, an
    expression id (ThingLit, no Literal).

    Any ambiguity — a duplicated value among tokens (`person:5 ... WHERE
    n = 5`) or among nodes, a token folded into a non-Literal (negative
    ids and numbers) — demotes that token to a fixed position; a variant
    with no slots still serves any literal-identical respelling
    (case/whitespace) plus its routes."""
    from surrealdb_tpu.sql import ast as A
    from surrealdb_tpu.sql.value import Thing
    from surrealdb_tpu.syn import parser as _parser

    lexed = _parser.lex_literal_slots(text)
    if lexed is None:
        return None
    kinds, values = lexed
    sites = _collect_literal_sites(query)
    taken: set = set()
    slot_sites: List[Tuple[int, Tuple[Any, Any, Any]]] = []
    fixed: List[Tuple[int, Any]] = []
    bindable = [
        i for i, k in enumerate(kinds) if k in _parser.BINDABLE_TOKEN_KINDS
    ]
    for i in bindable:
        v = values[i]
        dup = any(j != i and _fixed_eq(values[j], v) for j in bindable)
        tb = values[i - 1] if i and kinds[i - 1] == "IDENT" else None
        matches = [
            s for s in sites
            if id(s[2]) not in taken and _carries(s[2].value, v, tb)
        ]
        if dup or len(matches) != 1:
            fixed.append((i, v))
            continue
        taken.add(id(matches[0][2]))
        slot_sites.append((i, matches[0]))
    for i, k in enumerate(kinds):
        if k not in _parser.BINDABLE_TOKEN_KINDS:
            fixed.append((i, values[i]))
    fixed.sort()
    id_types: List[Tuple[int, type]] = []
    for slot, (i, (container, key, node)) in enumerate(slot_sites):
        if type(node.value) is Thing:
            sl = A.SlotThing(slot, node.value)
            id_types.append((i, type(values[i])))
        else:
            sl = A.SlotLiteral(slot, node.value)
        if isinstance(container, list):
            container[key] = sl
        elif isinstance(container, dict):
            container[key] = sl
        else:
            setattr(container, key, sl)
    return _Variant(
        query,
        kinds,
        tuple(fixed),
        tuple(i for i, _ in slot_sites),
        tuple(id_types),
        tuple(values[i] for i, _ in slot_sites),
        _stmt_key(text)[:200],
    )


def _scope_key(session) -> Tuple:
    """The tenant/session scope a route is valid for — a cached plan must
    never leak across namespaces, databases, or privilege levels."""
    a = getattr(session, "auth", None)
    return (
        getattr(session, "ns", None),
        getattr(session, "db", None),
        getattr(a, "level", None),
        tuple(getattr(a, "roles", ()) or ()),
        getattr(a, "access", None),
        str(getattr(a, "rid", None)),
    )


# ------------------------------------------------------------------ cache
class PlanCache:
    """Per-datastore plan & pipeline cache. All state behind `_lock`
    (`plan_cache.store`, locks.HIERARCHY 85); every mutation goes through
    the public methods below — graftlint GL015's single write door."""

    def __init__(self, ds):
        from surrealdb_tpu import cnf

        self.enabled = bool(getattr(cnf, "PLAN_CACHE", True))
        self._cap = max(int(getattr(cnf, "PLAN_CACHE_CAP", 512)), 8)
        self._min_hits = max(int(getattr(cnf, "PLAN_CACHE_MIN_HITS", 2)), 1)
        self._ds = weakref.ref(ds)
        self._lock = _locks.Lock("plan_cache.store")
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self._warm: "OrderedDict[str, int]" = OrderedDict()  # fp -> observes
        self._by_stmt: Dict[int, Tuple[str, _Variant]] = {}
        self._index_defs: "OrderedDict[Tuple, Tuple[Tuple, list]]" = (
            OrderedDict()
        )  # (ns, db, tb) -> (gen token, raw defs)
        self._gen: Dict[Tuple, int] = {}  # (ns, db) -> schema generation
        self._inflight: Dict[Tuple, int] = {}  # (ns, db) -> DDLs in flight
        self._epoch: Any = None  # cluster membership epoch, None standalone
        self._hits = {"ast": 0, "route": 0}
        self._misses: Counter = Counter()
        self._invalidations: Counter = Counter()
        self._verifies = {"ok": 0, "failed": 0}
        self._evlog: deque = deque(maxlen=_EVLOG_CAP)
        _caches.add(self)

    # ------------------------------------------------------- AST serve
    def fetch(self, text: str) -> Optional[Served]:
        """The parser cache-front (ds.execute_local). Returns a warm
        Served or None (caller parses cold and calls observe())."""
        if not self.enabled:
            return None
        from surrealdb_tpu import stats

        key = _stmt_key(text)
        if not key or ";" in key:
            return None  # empty or multi-statement: never cached
        fp, _ = stats.fingerprint(key)
        dg = _digest(key)
        with self._lock:
            entry = self._entries.get(fp)
            if entry is None:
                self._misses["cold"] += 1
                out = None
            else:
                self._entries.move_to_end(fp)
                out = self._serve_digest(entry, dg)
        if out is None and entry is not None:
            out = self._serve_lexed(entry, fp, key, dg)
        if out is not None:
            self._inc_hit("ast")
        elif entry is None:
            self._inc_miss("cold")
        return out

    def _serve_digest(self, entry: _Entry, dg: str) -> Optional[Served]:
        """Exact-text hit: no lexing, no binding derivation. Lock held."""
        for v in entry.variants:
            if dg in v.digests:
                v.digests.move_to_end(dg)
                entry.hits += 1
                self._hits["ast"] += 1
                return Served(v.query, v.digests[dg], entry.fp, "digest")
        return None

    def _serve_lexed(
        self, entry: _Entry, fp: str, key: str, dg: str
    ) -> Optional[Served]:
        """New spelling of a cached shape: lex, match a variant's
        signature, bind slot values — verifying against a fresh parse
        until the variant has earned trust."""
        from surrealdb_tpu.syn import parser as _parser

        lexed = _parser.lex_literal_slots(key)
        if lexed is None:
            with self._lock:
                entry.misses += 1
                self._misses["unlexable"] += 1
            self._inc_miss("unlexable")
            return None
        kinds, values = lexed
        with self._lock:
            match: Optional[_Variant] = None
            for v in entry.variants:
                if (
                    v.kinds == kinds
                    and all(_fixed_eq(values[i], fv) for i, fv in v.fixed)
                    and all(type(values[i]) is t for i, t in v.id_types)
                ):
                    match = v
                    break
            if match is None or (not match.parameterized and match.digests):
                # unparameterized variants serve by digest only — a new
                # spelling means a genuinely different statement
                entry.misses += 1
                self._misses["variant"] += 1
                cause = "variant"
            else:
                slots = tuple(values[i] for i in match.slot_idx)
                trusted = match.trust >= _VERIFY_TRUST
        if match is None or (not match.parameterized and match.digests):
            self._inc_miss(cause)
            return None
        if not trusted and not self._verify(match, key, slots):
            return None
        with self._lock:
            entry.hits += 1
            self._hits["ast"] += 1
            if len(match.digests) >= _DIGEST_CAP:
                match.digests.popitem(last=False)
            match.digests[dg] = slots or None
        return Served(match.query, slots or None, fp, "lexed")

    def _verify(self, variant: _Variant, key: str, slots: Tuple) -> bool:
        """Parse `key` fresh and prove the bound template reproduces it.
        Success builds trust; ONE failure demotes the variant to
        exact-digest serving for good (cause `verify`)."""
        from surrealdb_tpu.syn import parse_query

        try:
            fresh = parse_query(key)
        except Exception:
            return False
        ok = len(fresh.statements) == 1 and _ast_equal(
            variant.stmt, fresh.statements[0], slots
        )
        with self._lock:
            if ok:
                variant.trust += 1
                self._verifies["ok"] += 1
            else:
                variant.parameterized = False
                variant.trust = 0
                self._verifies["failed"] += 1
                self._invalidations["verify"] += 1
        if not ok:
            self._inc_invalidation("verify")
            self._inc_miss("verify")
        return ok

    def observe(self, text: str, query) -> None:
        """The cold-parse report (ds.execute_local): counts the shape and,
        once it has been seen `_MIN_HITS` times, installs the parsed
        query as a shared template (parameterized in place — SlotLiteral
        defaults keep this very execution's values)."""
        if not self.enabled:
            return
        from surrealdb_tpu import stats

        if len(query.statements) != 1 or not _cacheable(query.statements[0]):
            return
        key = _stmt_key(text)
        if not key or ";" in key:
            return
        fp, _ = stats.fingerprint(key)
        with self._lock:
            n = self._warm.get(fp, 0) + 1
            self._warm[fp] = n
            self._warm.move_to_end(fp)
            while len(self._warm) > self._cap * 4:
                self._warm.popitem(last=False)
            if n < self._min_hits:
                return
            entry = self._entries.get(fp)
            if entry is not None and entry.churn > 8 and not entry.bound:
                # a high-cardinality shape no spelling of which has ever
                # bound (`likes:8abc2`, folded literals): one more
                # exact-text variant would only thrash the slots, so
                # neither lex nor walk for it. Every `_REVALIDATE_EVERY`th
                # is looked at all the same: no verdict is pinned forever
                entry.refused += 1
                if entry.refused % _REVALIDATE_EVERY:
                    return
        variant = _parameterize(text, query)
        if variant is None:
            return
        evicted: List[Tuple[str, str]] = []
        dg = _digest(key)
        with self._lock:
            entry = self._entries.get(fp)
            if entry is None:
                entry = self._entries[fp] = _Entry(fp)
            self._entries.move_to_end(fp)
            for v in entry.variants:
                if v.kinds == variant.kinds and len(v.fixed) == len(
                    variant.fixed
                ) and all(
                    i == j and _fixed_eq(a, b)
                    for (i, a), (j, b) in zip(v.fixed, variant.fixed)
                ):
                    # raced install of the same spelling: keep the winner
                    return
            if entry.churn > 8 and not variant.parameterized:
                # the same refusal where the shape has a spelling that
                # binds beside this one that does not
                return
            while len(entry.variants) >= _VARIANT_CAP:
                old = entry.variants.pop(0)
                self._drop_variant(old)
                self._invalidations["capacity"] += 1
                entry.churn += 1
            entry.variants.append(variant)
            entry.bound = entry.bound or variant.parameterized
            variant.digests[dg] = variant.defaults or None
            self._by_stmt[id(variant.stmt)] = (fp, variant)
            while len(self._entries) > self._cap:
                old_fp, old_e = self._entries.popitem(last=False)
                for v in old_e.variants:
                    self._drop_variant(v)
                self._invalidations["capacity"] += 1
                self._evlog.append(
                    {"fp": old_fp, "cause": "capacity", "ts": time.time()}
                )
                evicted.append((old_fp, "capacity"))
        for efp, cause in evicted:
            self._emit_evict(efp, cause)

    def _drop_variant(self, v: _Variant) -> None:
        """Lock held: detach a variant's identity-map entry and routes."""
        self._by_stmt.pop(id(v.stmt), None)
        v.routes.clear()

    # ------------------------------------------------------- route serve
    def _route_for(self, ctx, stm) -> Optional[Tuple[str, _Variant, _Route]]:
        """Lock held by caller? No — takes the lock itself. Resolves the
        (fp, variant, route) for `stm` IF stm is a cached template
        statement and every validation stamp still matches."""
        o = self._by_stmt.get(id(stm))
        if o is None or o[1].stmt is not stm:
            return None
        fp, variant = o
        scope = _scope_key(getattr(ctx.executor, "session", None))
        route = variant.routes.get(scope)
        if route is None:
            return None
        ns, db, gen = route.gen
        if self._gen.get((ns, db), 0) != gen or self._inflight.get((ns, db)):
            del variant.routes[scope]
            self._invalidations["ddl"] += 1
            return ("ddl", variant, route)
        if route.epoch != self._epoch:
            del variant.routes[scope]
            self._invalidations["epoch"] += 1
            return ("epoch", variant, route)
        route.serves += 1
        if route.serves % _REVALIDATE_EVERY == 0:
            self._invalidations["revalidate"] += 1
            return ("revalidate", variant, route)
        return (fp, variant, route)

    def front_for(self, ctx, stm) -> Optional[str]:
        """The dispatch skeleton (stmt_exec.select_compute): which front
        resolved this shape cold, or None to run the full ladder."""
        if not self.enabled:
            return None
        cause = None
        with self._lock:
            res = self._route_for(ctx, stm)
            if res is None:
                return None
            tag, variant, route = res
            if tag in ("ddl", "epoch", "revalidate"):
                cause = tag
                front = None
            else:
                front = route.front
                self._hits["route"] += 1
                e = self._entries.get(tag)
                if e is not None:
                    e.route_hits += 1
        if cause is not None:
            self._inc_invalidation(cause)
            return None
        self._inc_hit("route")
        return front

    def note_front(self, ctx, stm, front: str) -> None:
        """Cold-ladder report: record which front resolved the template
        statement, under the generation token captured at statement
        start (refused while a DDL is in flight)."""
        if not self.enabled:
            return
        token = getattr(ctx.executor, "plan_gen", None)
        if token is None:
            return
        ns, db, gen = token
        with self._lock:
            o = self._by_stmt.get(id(stm))
            if o is None or o[1].stmt is not stm:
                return
            if (
                self._gen.get((ns, db), 0) != gen
                or self._inflight.get((ns, db))
            ):
                return
            variant = o[1]
            scope = _scope_key(getattr(ctx.executor, "session", None))
            route = variant.routes.get(scope)
            if route is None or route.front != front:
                route = _Route(front, token, self._epoch)
                while len(variant.routes) >= _SCOPE_CAP:
                    variant.routes.popitem(last=False)
                variant.routes[scope] = route
            else:
                route.gen = token
                route.epoch = self._epoch
                variant.routes.move_to_end(scope)

    def lowering_for(self, ctx, stm):
        """The cached ops/pipeline.Lowering for this template statement
        and scope, already stamp-validated — or None (cold analyze)."""
        if not self.enabled:
            return None
        with self._lock:
            o = self._by_stmt.get(id(stm))
            if o is None or o[1].stmt is not stm:
                return None
            scope = _scope_key(getattr(ctx.executor, "session", None))
            route = o[1].routes.get(scope)
            if route is None or route.front != "pipeline":
                return None
            ns, db, gen = route.gen
            if (
                self._gen.get((ns, db), 0) != gen
                or self._inflight.get((ns, db))
                or route.epoch != self._epoch
            ):
                return None  # front_for already counted the invalidation
            return route.lowering

    def install_lowering(self, ctx, stm, lowering) -> None:
        """Attach the cold-analyzed Lowering to the statement's pipeline
        route (note_front has just recorded the front)."""
        if not self.enabled:
            return
        with self._lock:
            o = self._by_stmt.get(id(stm))
            if o is None or o[1].stmt is not stm:
                return
            scope = _scope_key(getattr(ctx.executor, "session", None))
            route = o[1].routes.get(scope)
            if route is not None and route.front == "pipeline":
                route.lowering = lowering

    def install_pipeline(self, ctx, stm, lowering) -> None:
        """Cold pipeline resolve: record the front AND attach the
        Lowering in one door (ops/pipeline.run_pipeline)."""
        self.note_front(ctx, stm, "pipeline")
        self.install_lowering(ctx, stm, lowering)

    def drop_route(self, ctx, stm, cause: str) -> None:
        """A validated serve was declined downstream (the mirror said
        no): drop the route so the cold ladder re-resolves next time."""
        dropped = False
        with self._lock:
            o = self._by_stmt.get(id(stm))
            if o is not None and o[1].stmt is stm:
                scope = _scope_key(getattr(ctx.executor, "session", None))
                if o[1].routes.pop(scope, None) is not None:
                    self._invalidations[cause] += 1
                    dropped = True
        if dropped:
            self._inc_invalidation(cause)

    # ------------------------------------------------------- planner defs
    def index_defs_for(self, ctx, ns, db, tb) -> Optional[list]:
        """The cached raw `all_tb_indexes` probe for (ns, db, tb), valid
        only at the current schema generation with no DDL in flight."""
        if not self.enabled:
            return None
        key = (ns, db, tb)
        with self._lock:
            got = self._index_defs.get(key)
            if got is None:
                return None
            (gns, gdb, gen), defs = got
            if self._gen.get((gns, gdb), 0) != gen or self._inflight.get(
                (gns, gdb)
            ):
                del self._index_defs[key]
                self._invalidations["ddl"] += 1
                return None
            self._index_defs.move_to_end(key)
        return defs

    def install_index_defs(self, ctx, ns, db, tb, defs: list) -> None:
        token = getattr(
            getattr(ctx, "executor", None), "plan_gen", None
        ) or (ns, db, self._gen.get((ns, db), 0))
        tns, tdb, gen = token
        if (tns, tdb) != (ns, db):
            return  # a USE switched scope mid-statement: don't stamp-mix
        with self._lock:
            if self._gen.get((ns, db), 0) != gen or self._inflight.get(
                (ns, db)
            ):
                return
            self._index_defs[(ns, db, tb)] = (token, list(defs))
            while len(self._index_defs) > self._cap:
                self._index_defs.popitem(last=False)

    # ------------------------------------------------------- invalidation
    def gen_token(self, ns, db) -> Tuple:
        """The generation token an executor captures at statement start;
        installs made under a stale or in-flight token are refused, which
        closes the DDL-commit-to-bump race."""
        if self._inflight.get((ns, db)):
            return (ns, db, -1)  # never matches: a DDL is in flight
        return (ns, db, self._gen.get((ns, db), 0))

    def ddl_begin(self, ns, db) -> None:
        """Bracket a schema change: bump the generation (invalidating
        every pre-DDL artifact lazily) and refuse installs until
        ddl_end's second bump covers anything raced in between."""
        with self._lock:
            self._gen[(ns, db)] = self._gen.get((ns, db), 0) + 1
            self._inflight[(ns, db)] = self._inflight.get((ns, db), 0) + 1

    def ddl_end(self, ns, db) -> None:
        with self._lock:
            self._gen[(ns, db)] = self._gen.get((ns, db), 0) + 1
            n = self._inflight.get((ns, db), 0) - 1
            if n > 0:
                self._inflight[(ns, db)] = n
            else:
                self._inflight.pop((ns, db), None)
        self._inc_invalidation("ddl")

    def bump_generation(self, ns, db) -> None:
        """One-shot generation bump for schema changes that are not
        statement-bracketed (the async index builder's ready flip)."""
        with self._lock:
            self._gen[(ns, db)] = self._gen.get((ns, db), 0) + 1
        self._inc_invalidation("ddl")

    def on_plan_flip(self, fp: str) -> None:
        """stats.record detected a plan-mix flip: the shape's cached
        decision is now suspect — evict the whole entry."""
        with self._lock:
            entry = self._entries.pop(fp, None)
            if entry is not None:
                for v in entry.variants:
                    self._drop_variant(v)
                self._invalidations["flip"] += 1
                self._evlog.append(
                    {"fp": fp, "cause": "flip", "ts": time.time()}
                )
        if entry is not None:
            self._inc_invalidation("flip")
            self._emit_evict(fp, "flip")

    def note_epoch(self, epoch) -> None:
        """Cluster membership changed: every route resolved under the old
        ring is invalid (scatter targets moved)."""
        emit = False
        with self._lock:
            if self._epoch != epoch:
                emit = self._epoch is not None and bool(self._entries)
                self._epoch = epoch
                if emit:
                    self._invalidations["epoch"] += 1
        if emit:
            self._inc_invalidation("epoch")
            self._emit_evict(None, "epoch")

    def clear(self) -> None:
        """Drop everything (tests, cold measurement windows)."""
        with self._lock:
            self._entries.clear()
            self._warm.clear()
            self._by_stmt.clear()
            self._index_defs.clear()

    def reset_window(self) -> None:
        """Zero counters but KEEP entries — a warm measurement window
        starts here."""
        with self._lock:
            self._hits = {"ast": 0, "route": 0}
            self._misses.clear()
            self._invalidations.clear()
            self._verifies = {"ok": 0, "failed": 0}
            for e in self._entries.values():
                e.hits = e.misses = e.route_hits = 0

    # ------------------------------------------------------- views
    def snapshot(self, limit: int = 20) -> dict:
        """The debug bundle's `plan_cache` section."""
        with self._lock:
            rows = []
            for fp, e in list(self._entries.items())[-limit:]:
                rows.append(
                    {
                        "fingerprint": fp,
                        "sql": e.variants[0].text if e.variants else None,
                        "variants": len(e.variants),
                        "hits": e.hits,
                        "route_hits": e.route_hits,
                        "misses": e.misses,
                        "routes": sum(
                            len(v.routes) for v in e.variants
                        ),
                        "fronts": sorted(
                            {
                                r.front
                                for v in e.variants
                                for r in v.routes.values()
                            }
                        ),
                        "parameterized": any(
                            v.parameterized for v in e.variants
                        ),
                    }
                )
            state = {
                "enabled": self.enabled,
                "cap": self._cap,
                "min_hits": self._min_hits,
                "entries": len(self._entries),
                "hits": dict(self._hits),
                "misses": dict(self._misses),
                "invalidations": dict(self._invalidations),
                "verifies": dict(self._verifies),
                "epoch": self._epoch,
                "generations": {
                    f"{ns}/{db}": g for (ns, db), g in self._gen.items()
                },
                "recent_evictions": list(self._evlog)[-16:],
            }
        state["top"] = rows[::-1]
        return state

    def describe(self, fp: str) -> Optional[dict]:
        """One fingerprint's cache state — the /statements annotation."""
        with self._lock:
            e = self._entries.get(fp)
            if e is None:
                n = self._warm.get(fp)
                return {"cached": False, "observed": n} if n else None
            return {
                "cached": True,
                "variants": len(e.variants),
                "hits": e.hits,
                "route_hits": e.route_hits,
                "misses": e.misses,
                "fronts": sorted(
                    {
                        r.front
                        for v in e.variants
                        for r in v.routes.values()
                    }
                ),
            }

    def annotate(self, rows: List[dict]) -> List[dict]:
        """Attach `plan_cache` state to /statements rows in place."""
        for row in rows:
            fp = row.get("fingerprint")
            if fp and "plan_cache" not in row:
                got = self.describe(fp)
                if got is not None:
                    row["plan_cache"] = got
        return rows

    # ------------------------------------------------------- emission
    # One helper per metric family so every emission site carries a STATIC
    # name and STATIC label keys (GL006: bounded series cardinality); the
    # variable part rides the label VALUE. All are called outside the
    # store lock (locks.HIERARCHY: telemetry and events are peers/lower
    # leaves — never nest under us).
    def _inc_hit(self, kind: str) -> None:
        from surrealdb_tpu import telemetry

        telemetry.inc("plan_cache_hits", kind=kind)

    def _inc_miss(self, cause: str) -> None:
        from surrealdb_tpu import telemetry

        telemetry.inc("plan_cache_misses", cause=cause)

    def _inc_invalidation(self, cause: str) -> None:
        from surrealdb_tpu import telemetry

        telemetry.inc("plan_cache_invalidations", cause=cause)

    def _emit_evict(self, fp: Optional[str], cause: str) -> None:
        from surrealdb_tpu import events

        events.emit("plan_cache.evict", fingerprint=fp, cause=cause)


# ------------------------------------------------------------------ registry
# every live PlanCache, so stats.record's flip hook (which has no ds
# handle) can reach them all
_caches: "weakref.WeakSet[PlanCache]" = weakref.WeakSet()


def on_plan_flip(fp: str) -> None:
    """stats.record's post-lock flip hook: evict `fp` everywhere."""
    for pc in list(_caches):
        pc.on_plan_flip(fp)


def active_plan_cache(ctx) -> Optional[PlanCache]:
    """The executing statement's datastore cache, or None (no executor on
    the context / cache disabled)."""
    ex = getattr(ctx, "executor", None)
    ds = getattr(ex, "ds", None)
    pc = getattr(ds, "plan_cache", None)
    if pc is not None and pc.enabled:
        return pc
    return None
