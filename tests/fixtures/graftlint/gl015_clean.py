"""GL015 clean twin: plan-cache access through the public doors only."""

from surrealdb_tpu.dbs import plan_cache


def serve_or_observe(ds, text, query, parse_us):
    served = ds.plan_cache.fetch(text)
    if served is None:
        ds.plan_cache.observe(text, query, parse_us)
    return served


def ddl_bracket(ds, ns, db):
    # the generation protocol goes through the bracket methods
    ds.plan_cache.ddl_begin(ns, db)
    try:
        pass
    finally:
        ds.plan_cache.ddl_end(ns, db)


def invalidate(ds, fp, epoch):
    ds.plan_cache.on_plan_flip(fp)
    ds.plan_cache.note_epoch(epoch)
    ds.plan_cache.bump_generation("ns", "db")
    plan_cache.on_plan_flip(fp)


def read_views(ds):
    # read surfaces are public API, not store pokes
    return (
        ds.plan_cache.snapshot(limit=5),
        ds.plan_cache.describe(fp="0" * 16),
    )
