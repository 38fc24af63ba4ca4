"""Share of the traced slice's device-operation time under the engine step
body's ``kv_cache_update`` and ``kv_cow`` scopes: the KV append, the
copy-on-write ``conditional`` and the pool-sized layout copies the compiler
hangs on them."""
NAME, UNIT, LAYER, MOVES = "kv_pool_copy_pct.serve", "%", "serving host", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.scope_share_pct(run, phases.KV_POOL_SCOPES)
