"""GL015 fixture: ad-hoc plan-cache state mutation."""

import surrealdb_tpu.dbs.plan_cache
import surrealdb_tpu.dbs.plan_cache as pc
from surrealdb_tpu.dbs import plan_cache


def sneak_install(ds, fp, entry):
    # reaching into the entry table bypasses the validation-on-serve
    # stamps — a plan installed here can serve stale after a DDL
    with ds.plan_cache._lock:
        ds.plan_cache._entries[fp] = entry
        ds.plan_cache._hits["ast"] += 1


def sneak_generation(ctx, ns, db):
    # un-bumping a generation re-arms every plan a DDL just invalidated
    ctx.executor.ds.plan_cache._gen[(ns, db)] = 0
    ctx.executor.ds.plan_cache._inflight.clear()


def sneak_module_state():
    # the module-level registry is private too
    plan_cache._caches.clear()
    pc._caches.clear()
    return surrealdb_tpu.dbs.plan_cache._caches


def sneak_counters(ds):
    # cooking the counters lies to whoever reads snapshot()
    ds.plan_cache._misses.clear()
    ds.plan_cache._evlog.clear()
