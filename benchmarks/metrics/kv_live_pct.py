"""Share of the KV pool that live requests hold, mean over the window's engine
steps: blocks allocated minus those the prefix cache would surrender
(``engine.pool_stats()`` after every pump), over the pool's blocks. What is
left is reservation: the pool is sized for ``max_slots`` sequences of
``max_model_len``, the traffic's are shorter."""
NAME, UNIT, LAYER, MOVES = "kv_live_pct", "%", "serving host", "itl_p95_ms"


def read(run):
    pumps, total = run.get("window_pumps"), run["counters"].get("num_blocks")
    if not pumps or not total:
        return None
    return 100.0 * sum(p[3] for p in pumps) / (len(pumps) * total)
