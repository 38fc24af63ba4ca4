"""Median time to first token over the window's requests, from the due time
(the benchmark's own client stamps, host clock): the body of the distribution
beside the tail that ``ttft_p95_ms`` judges. The median prompt's prefill steps
plus the wait for a slot."""
NAME, UNIT, LAYER, MOVES = "ttft_p50_ms", "ms", "serving host", "ttft_p95_ms"


def read(run):
    return (run.get("beside") or {}).get("ttft_p50_ms")
