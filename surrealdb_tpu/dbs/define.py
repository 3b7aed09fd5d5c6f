"""DEFINE / REMOVE / ALTER / REBUILD execution.

Role of the reference's define/remove/alter statement computes (reference:
core/src/sql/statements/define/, remove/, alter/): persist catalog
definitions into the keyspace and run side effects (index builds, view
bootstraps).
"""

from __future__ import annotations

import secrets
from typing import Any, Optional

from surrealdb_tpu import key as keys
from surrealdb_tpu.err import IxNotFoundError, SurrealError, TbNotFoundError
from surrealdb_tpu.sql.value import NONE, Thing


class _AlreadyExists(SurrealError):
    def __init__(self, kind: str, name: str):
        super().__init__(f"The {kind} '{name}' already exists")


def _guard(existing, args, kind: str, name: str) -> bool:
    """Handle IF NOT EXISTS / OVERWRITE. Returns True when the define should
    be skipped."""
    if existing is not None:
        if args.get("if_not_exists"):
            return True
        if not args.get("overwrite"):
            raise _AlreadyExists(kind, name)
    return False


def define_compute(ctx, stm) -> Any:
    from surrealdb_tpu.iam.check import check_ddl

    kind = stm.kind
    target_base = stm.args.get("base") if kind in ("user", "access") else None
    check_ddl(ctx, kind, target_base=target_base)
    args = stm.args
    handler = _DEFINES.get(kind)
    if handler is None:
        raise SurrealError(f"DEFINE {kind.upper()} is not supported")
    return handler(ctx, args)


# ------------------------------------------------------------------ handlers
def _def_namespace(ctx, a) -> Any:
    txn = ctx.txn()
    name = a["name"]
    if _guard(txn.get_ns(name), a, "namespace", name):
        return NONE
    txn.put_ns(name, {"name": name, "comment": a.get("comment")})
    return NONE


def _def_database(ctx, a) -> Any:
    txn = ctx.txn()
    ns = ctx.session.ns
    name = a["name"]
    txn.ensure_ns(ns)
    if _guard(txn.get_db(ns, name), a, "database", name):
        return NONE
    txn.put_db(ns, name, {
        "name": name,
        "changefeed": a.get("changefeed"),
        "comment": a.get("comment"),
    })
    return NONE


def _def_table(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    name = a["name"]
    txn.ensure_db(ns, db)
    if _guard(txn.get_tb(ns, db, name), a, "table", name):
        return NONE
    d = {
        "name": name,
        "drop": a.get("drop", False),
        "schemafull": a.get("schemafull", False),
        "kind": a.get("kind", "ANY"),
        "relation_in": a.get("relation_in"),
        "relation_out": a.get("relation_out"),
        "enforced": a.get("enforced", False),
        "view": a.get("view"),
        "permissions": a.get("permissions"),
        "changefeed": a.get("changefeed"),
        "comment": a.get("comment"),
    }
    txn.put_tb(ns, db, name, d)
    if d["view"] is not None:
        _bootstrap_view(ctx, name, d["view"])
    return NONE


def _bootstrap_view(ctx, view_name: str, sel) -> None:
    """Register the view link on each source table and materialize the
    initial contents (reference: doc/table.rs foreign tables)."""
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    from surrealdb_tpu.sql.value import Table
    from surrealdb_tpu.sql.path import Idiom, PField

    for w in sel.what:
        src = w.compute(ctx)
        if isinstance(src, Table):
            txn.ensure_tb(ns, db, str(src))
            txn.put_tb_view(ns, db, str(src), view_name, {"name": view_name})
    from surrealdb_tpu.doc.views import materialize_view

    materialize_view(ctx, view_name, sel)


def _def_field(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    tb = a["table"]
    name = repr(a["name"]) if not isinstance(a["name"], str) else a["name"]
    txn.ensure_tb(ns, db, tb)
    if _guard(txn.get_tb_field(ns, db, tb, name), a, "field", name):
        return NONE
    txn.put_tb_field(ns, db, tb, name, {
        "name": name,
        "table": tb,
        "flex": a.get("flex", False),
        "kind": a.get("kind"),
        "readonly": a.get("readonly", False),
        "value": a.get("value"),
        "assert": a.get("assert"),
        "default": a.get("default"),
        "default_always": a.get("default_always", False),
        "permissions": a.get("permissions"),
        "comment": a.get("comment"),
    })
    return NONE


def _def_index(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    tb = a["table"]
    name = a["name"]
    txn.ensure_tb(ns, db, tb)
    if _guard(txn.get_tb_index(ns, db, tb, name), a, "index", name):
        return NONE
    concurrent = bool(a.get("concurrently"))
    d = {
        "name": name,
        "table": tb,
        "fields": a.get("fields", []),
        "index": a.get("index", {"type": "idx"}),
        "comment": a.get("comment"),
        "status": "building" if concurrent else "ready",
    }
    txn.put_tb_index(ns, db, tb, name, d)
    if concurrent:
        # async initial build (reference kvs/index.rs): kick AFTER this
        # transaction commits so the builder's txns see the definition;
        # the planner refuses the index until its status flips to ready
        ds = ctx.ds()
        sess = ctx.session

        txn.on_commit(lambda: ds.index_builder.build(ns, db, tb, d, sess))
        return NONE
    # inline build over existing records
    from surrealdb_tpu.idx.index import rebuild_index

    rebuild_index(ctx, tb, d)
    return NONE


def _def_event(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    tb = a["table"]
    name = a["name"]
    txn.ensure_tb(ns, db, tb)
    if _guard(txn.get_tb_event(ns, db, tb, name), a, "event", name):
        return NONE
    txn.put_tb_event(ns, db, tb, name, {
        "name": name,
        "table": tb,
        "when": a.get("when"),
        "then": a.get("then", []),
        "comment": a.get("comment"),
    })
    return NONE


def _def_analyzer(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    name = a["name"]
    txn.ensure_db(ns, db)
    if _guard(txn.get_az(ns, db, name), a, "analyzer", name):
        return NONE
    txn.put_az(ns, db, name, {
        "name": name,
        "tokenizers": a.get("tokenizers", []),
        "filters": a.get("filters", []),
        "function": a.get("function"),
        "comment": a.get("comment"),
    })
    return NONE


def _def_function(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    name = a["name"]
    txn.ensure_db(ns, db)
    if _guard(txn.get_fc(ns, db, name), a, "function", name):
        return NONE
    txn.put_fc(ns, db, name, {
        "name": name,
        "params": a.get("params", []),
        "body": a.get("body"),
        "returns": a.get("returns"),
        "permissions": a.get("permissions"),
        "comment": a.get("comment"),
    })
    return NONE


def _def_param(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    name = a["name"]
    txn.ensure_db(ns, db)
    if _guard(txn.get_pa(ns, db, name), a, "param", name):
        return NONE
    value = a.get("value")
    if value is not None and hasattr(value, "compute"):
        value = value.compute(ctx)
    txn.put_pa(ns, db, name, {
        "name": name,
        "value": value,
        "permissions": a.get("permissions"),
        "comment": a.get("comment"),
    })
    return NONE


def _def_user(ctx, a) -> Any:
    txn = ctx.txn()
    name = a["name"]
    base = a.get("base", "root")

    # resolve the existence guard BEFORE paying the KDF cost
    if base == "root":
        existing = txn.get_root_user(name)
    elif base == "ns":
        txn.ensure_ns(ctx.session.ns)
        existing = txn.get_ns_user(ctx.session.ns, name)
    else:
        ns, db = ctx.ns_db()
        txn.ensure_db(ns, db)
        existing = txn.get_db_user(ns, db, name)
    if _guard(existing, a, "user", name):
        return NONE

    from surrealdb_tpu.iam.password import hash_password

    password = a.get("password")
    passhash = a.get("passhash") or (hash_password(password) if password else None)
    d = {
        "name": name,
        "base": base,
        "hash": passhash,
        "roles": a.get("roles", ["Viewer"]),
        "token_duration": a.get("token_duration"),
        "session_duration": a.get("session_duration"),
        "comment": a.get("comment"),
    }
    if base == "root":
        txn.put_root_user(name, d)
    elif base == "ns":
        txn.put_ns_user(ctx.session.ns, name, d)
    else:
        ns, db = ctx.ns_db()
        txn.put_db_user(ns, db, name, d)
    return NONE


def _def_access(ctx, a) -> Any:
    txn = ctx.txn()
    name = a["name"]
    base = a.get("base", "db")
    level = _access_level(ctx, base)
    if _guard(txn.get_access(level, name), a, "access", name):
        return NONE
    txn.put_access(level, name, {
        "name": name,
        "base": base,
        "access_type": a.get("access_type"),
        "signup": a.get("signup"),
        "signin": a.get("signin"),
        "authenticate": a.get("authenticate"),
        "jwt_alg": a.get("jwt_alg", "HS512"),
        # no WITH KEY → random secret, so issued tokens verify on the way back
        # in (reference: define/access.rs random_key())
        "jwt_key": a.get("jwt_key") or secrets.token_urlsafe(32),
        "jwt_url": a.get("jwt_url"),
        "jwt_issuer_key": a.get("jwt_issuer_key"),
        "token_duration": a.get("token_duration"),
        "session_duration": a.get("session_duration"),
        # unspecified -> 30d default (reference: access/DEFAULT_GRANT_DURATION);
        # explicit `DURATION FOR GRANT NONE` stores None (never expires)
        "grant_duration": a.get("grant_duration", 30 * 24 * 3600 * 1_000_000_000),
        "bearer_subject": a.get("bearer_subject"),
        "comment": a.get("comment"),
    })
    return NONE


def _access_level(ctx, base: str) -> tuple:
    if base == "root":
        return ()
    if base == "ns":
        return (ctx.session.ns,)
    return ctx.ns_db()


def _def_model(ctx, a) -> Any:
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    name, version = a["name"], a.get("version", "")
    txn.ensure_db(ns, db)
    existing = txn.get_ml(ns, db, name, version)
    if _guard(existing, a, "model", name):
        return NONE
    d = {
        "name": name,
        "version": version,
        "permissions": a.get("permissions"),
        "comment": a.get("comment"),
    }
    if existing:  # OVERWRITE re-defines metadata but keeps stored weights
        for k in ("blob", "in_dim", "out_dim"):
            if k in existing:
                d[k] = existing[k]
    txn.put_ml(ns, db, name, version, d)
    return NONE


def _def_config(ctx, a) -> Any:
    return NONE


_DEFINES = {
    "namespace": _def_namespace,
    "database": _def_database,
    "table": _def_table,
    "field": _def_field,
    "index": _def_index,
    "event": _def_event,
    "analyzer": _def_analyzer,
    "function": _def_function,
    "param": _def_param,
    "user": _def_user,
    "access": _def_access,
    "model": _def_model,
    "config": _def_config,
}


# ------------------------------------------------------------------ REMOVE
def remove_compute(ctx, stm) -> Any:
    from surrealdb_tpu.iam.check import check_ddl

    kind, name = stm.kind, stm.name
    target_base = (stm.level or "root") if kind in ("user", "access") else None
    check_ddl(ctx, kind, target_base=target_base)
    txn = ctx.txn()

    def missing(what: str):
        if stm.if_exists:
            return NONE
        raise SurrealError(f"The {what} '{name}' does not exist")

    if kind == "namespace":
        if txn.get_ns(name) is None:
            return missing("namespace")
        from surrealdb_tpu.key.encode import prefix_end

        txn.del_ns(name)
        pre = keys._ns(name)
        txn.delr(pre, prefix_end(pre))
        txn.touch_scope((name,))
        ds = ctx.ds()
        from surrealdb_tpu.ml.exec import invalidate_ns as _ml_invalidate_ns

        txn.on_commit(lambda: ds.graph_mirrors.drop_ns(name))
        txn.on_commit(lambda: ds.index_stores.remove_ns(name))
        txn.on_commit(lambda: ds.column_mirrors.drop_ns(name))
        txn.on_commit(lambda: _ml_invalidate_ns(ds, name))
        return NONE
    if kind == "database":
        ns = ctx.session.ns
        if txn.get_db(ns, name) is None:
            return missing("database")
        from surrealdb_tpu.key.encode import prefix_end

        txn.del_db(ns, name)
        pre = keys._db(ns, name)
        txn.delr(pre, prefix_end(pre))
        txn.touch_scope((ns, name))
        ds = ctx.ds()
        from surrealdb_tpu.ml.exec import invalidate_db as _ml_invalidate_db

        txn.on_commit(lambda: ds.graph_mirrors.drop_db(ns, name))
        txn.on_commit(lambda: ds.index_stores.remove_db(ns, name))
        txn.on_commit(lambda: ds.column_mirrors.drop_db(ns, name))
        txn.on_commit(lambda: _ml_invalidate_db(ds, ns, name))
        return NONE
    if kind == "table":
        ns, db = ctx.ns_db()
        if txn.get_tb(ns, db, name) is None:
            return missing("table")
        from surrealdb_tpu.key.encode import prefix_end

        txn.del_tb(ns, db, name)
        pre = keys.table_all_prefix(ns, db, name)
        txn.delr(pre, prefix_end(pre))
        txn.touch_scope((ns, db, name))
        ds = ctx.ds()
        txn.on_commit(lambda: ds.index_stores.remove_table(ns, db, name))
        txn.on_commit(lambda: ds.graph_mirrors.drop_table(ns, db, name))
        txn.on_commit(lambda: ds.column_mirrors.drop_table(ns, db, name))
        return NONE
    if kind == "field":
        ns, db = ctx.ns_db()
        if txn.get_tb_field(ns, db, stm.table, name) is None:
            return missing("field")
        txn.del_tb_field(ns, db, stm.table, name)
        return NONE
    if kind == "index":
        ns, db = ctx.ns_db()
        if txn.get_tb_index(ns, db, stm.table, name) is None:
            return missing("index")
        from surrealdb_tpu.key.encode import prefix_end

        txn.del_tb_index(ns, db, stm.table, name)
        pre = keys.index_prefix(ns, db, stm.table, name)
        txn.delr(pre, prefix_end(pre))
        ds = ctx.ds()
        txn.on_commit(lambda: ds.index_stores.remove(ns, db, stm.table, name))
        return NONE
    if kind == "event":
        ns, db = ctx.ns_db()
        if txn.get_tb_event(ns, db, stm.table, name) is None:
            return missing("event")
        txn.del_tb_event(ns, db, stm.table, name)
        return NONE
    if kind == "analyzer":
        ns, db = ctx.ns_db()
        if txn.get_az(ns, db, name) is None:
            return missing("analyzer")
        txn.del_az(ns, db, name)
        return NONE
    if kind == "function":
        ns, db = ctx.ns_db()
        fname = name
        if txn.get_fc(ns, db, fname) is None:
            return missing("function")
        txn.del_fc(ns, db, fname)
        return NONE
    if kind == "param":
        ns, db = ctx.ns_db()
        if txn.get_pa(ns, db, name) is None:
            return missing("param")
        txn.del_pa(ns, db, name)
        return NONE
    if kind == "user":
        base = stm.level or "root"
        if base == "root":
            if txn.get_root_user(name) is None:
                return missing("user")
            txn.del_root_user(name)
        elif base == "ns":
            ns = ctx.session.ns
            if txn.get_ns_user(ns, name) is None:
                return missing("user")
            txn.del_ns_user(ns, name)
        else:
            ns, db = ctx.ns_db()
            if txn.get_db_user(ns, db, name) is None:
                return missing("user")
            txn.del_db_user(ns, db, name)
        return NONE
    if kind == "access":
        level = _access_level(ctx, stm.level or "db")
        if txn.get_access(level, name) is None:
            return missing("access")
        txn.del_access(level, name)
        return NONE
    if kind == "model":
        ns, db = ctx.ns_db()
        version = getattr(stm, "table", None) or ""
        entry = txn.get_ml(ns, db, name, version)
        if entry is None:
            return missing("model")
        txn.del_ml(ns, db, name, version)
        # GC the content-addressed weights blob unless another model version
        # still references the same digest (ADVICE r2: orphaned blobs)
        digest = entry.get("blob")
        if digest and not any(m.get("blob") == digest for m in txn.all_ml(ns, db)):
            from surrealdb_tpu.obs import del_blob

            del_blob(txn, ns, db, digest)
        ds = ctx.ds()
        from surrealdb_tpu.ml.exec import invalidate as _ml_invalidate

        txn.on_commit(lambda: _ml_invalidate(ds, ns, db, name, version))
        return NONE
    raise SurrealError(f"REMOVE {kind.upper()} is not supported")


# ------------------------------------------------------------------ ALTER / REBUILD
def alter_compute(ctx, stm) -> Any:
    from surrealdb_tpu.iam.check import check_ddl

    check_ddl(ctx, stm.kind)
    if stm.kind != "table":
        raise SurrealError(f"ALTER {stm.kind.upper()} is not supported")
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    d = txn.get_tb(ns, db, stm.name)
    if d is None:
        if stm.if_exists:
            return NONE
        raise TbNotFoundError(stm.name)
    for k, v in stm.args.items():
        if v is not None and k in d:
            d[k] = v
    txn.put_tb(ns, db, stm.name, d)
    return NONE


def rebuild_compute(ctx, stm) -> Any:
    from surrealdb_tpu.iam.check import check_ddl

    check_ddl(ctx, "index")
    ns, db = ctx.ns_db()
    txn = ctx.txn()
    ix = txn.get_tb_index(ns, db, stm.table, stm.name)
    if ix is None:
        if stm.if_exists:
            return NONE
        raise IxNotFoundError(stm.name)
    from surrealdb_tpu.idx.index import rebuild_index

    rebuild_index(ctx, stm.table, ix)
    return NONE
