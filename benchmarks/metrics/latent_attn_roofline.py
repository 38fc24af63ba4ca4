"""The latent page walk's share of its roofline over the traced slice: the
LEAST the live work needs (``reference/mla_moe.py::latent_attention_least``:
for every live query row and every token it may see, each head's score over
the row and its weighing of the value, 2 x (576 + 512) flops at DeepSeek-V2's
widths; and each live page out of HBM once a set), ``max(bytes / HBM peak,
flops / bf16 peak)``, over the device time of the kernel's events, found by
their ``pallas_call`` name (``paged_latent_attention_*``).

Counted from the traffic and the program's counters, not from what the kernel
does, so it reads the same work whatever implements it and cannot pass 100:
live pages a step are the traced pumps' own (blocks the live requests hold,
``engine.pool_stats()`` after every pump); (row, key) pairs a page are the
window's ``engine.stats["attn_row_keys"]`` over ``["paged_pages_walked"]``.
A kernel that computes rows past ``q_lens`` (all ``C x heads`` of a slot)
reads lower for it. A program without the counter or the kernel reads nothing."""
NAME, UNIT, LAYER, MOVES = "latent_attn_roofline", "%", "Pallas kernels", "itl_p95_ms"

KERNELS = "paged_latent_attention_"


def read(run):
    from lib import arch, flops, phases

    trace = phases.program_trace(run)
    if trace is None or run["driver"] != "serve" or not run.get("traced_pumps"):
        return None
    cfg, c = run["cfg"], run["counters"]
    engine, ref = c.get("engine", {}), arch.reference(cfg)
    if not engine.get("attn_row_keys") or not engine.get("paged_pages_walked") or not hasattr(ref, "latent_attention_least"):
        return None
    devices = {d for _n, _a, _b, d in trace["ops"]}
    seconds = sum(b - a for n, a, b, _d in trace["ops"] if phases.in_family(n, KERNELS)) / max(len(devices), 1)
    if not seconds:
        return None
    pages = sum(p[3] for p in run["traced_pumps"])  # live pages, summed over the slice's steps
    least = ref.latent_attention_least(cfg, pages * engine["attn_row_keys"] / engine["paged_pages_walked"],
                                       pages * c["block_size"])
    sets = ref.attention_passes(cfg, run["depth"])
    return 100.0 * sets * flops.roofline_seconds(least["flops"], least["bytes"], run["peaks"])["seconds"] / seconds
