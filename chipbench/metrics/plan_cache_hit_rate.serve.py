"""plan_cache_hit_rate.serve: the plan cache's hits (a ready plan, or a
memoised tuner config) over its lookups in the window, from the
`PlanCache` counters."""


def read(r):
    s = getattr(r, "serve", None)
    if not s:
        return None
    hits = s["exact_hits"] + s["config_hits"]
    lookups = hits + s["misses"]
    return 100.0 * hits / lookups if lookups else None
