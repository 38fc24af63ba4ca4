"""Host time of one ``train_step(...)`` call up to its return, before the
sync: what tracing, state threading and launch cost per step. Median over the
window, from the benchmark's own ``train.dispatch`` span (host clock)."""
NAME, UNIT, LAYER, MOVES = "dispatch_ms.train", "ms", "train step capture", "train_tokens_per_s"


def read(run):
    d = run["spans"].durations(since=run["window"][0]).get("train.dispatch")
    if not d:
        return None
    d = sorted(d)
    return 1e3 * d[len(d) // 2]
