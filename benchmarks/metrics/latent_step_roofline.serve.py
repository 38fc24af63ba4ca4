"""How far a serving step of the latent-attention / expert model is from the
least it can do: ``reference/mla_moe.py::step_least`` (bytes: every held matrix
once, a routed expert ONLY where the window's ``engine.stats["moe_experts_hit"]``
says it got a row, each live latent row once a set, the step's embedding rows;
flops: two per matrix weight a live row multiplies through and the attention
calls' least), ``max(bytes / HBM peak, flops / bf16 peak)``, over the
device-busy time of a step: the union of the device's operations inside the
benchmark's ``frontend.pump`` spans of the traced slice, over their number, as
``hybrid_step_hbm_roofline.serve`` takes it. Live pages are the traced pumps'
mean (blocks the live requests hold); rows, (row, key) pairs and experts hit a
step are the window's means of the program's counters. It counts the least, so
it cannot pass 100. A program without the counters, or a configuration whose
reference file does not count them, reads nothing."""
NAME, UNIT, LAYER, MOVES = "latent_step_roofline.serve", "%", "model", "itl_p95_ms"


def read(run):
    from lib import arch, flops, xplane

    if not run.get("trace") or run["driver"] != "serve" or not run.get("traced_pumps"):
        return None
    cfg, c = run["cfg"], run["counters"]
    engine, ref = c.get("engine", {}), arch.reference(cfg)
    if "attn_row_keys" not in engine or "moe_experts_hit" not in engine or not engine.get("steps") or not hasattr(ref, "step_least"):
        return None
    raw = run["trace"]["raw"]
    pumps = [s for s in raw["spans"] if s[0] == "bench.frontend.pump"]
    if not pumps or not raw["devices"]:
        return None
    busy = [xplane.union([(a, b) for _n, a, b in ops]) for ops in raw["devices"].values()]
    inside = sum(min(b, d) - max(a, lo) for _n, a, b in pumps for dev in busy for lo, d in dev if d > a and lo < b)
    step_busy_s = inside / len(busy) / len(pumps)
    if not step_busy_s:
        return None
    steps, traced = engine["steps"], run["traced_pumps"]
    rows = (engine.get("prompt_tokens_computed", 0) + run["out_tokens_in_window"]) / steps
    live_tokens = c["block_size"] * sum(p[3] for p in traced) / len(traced)
    least = ref.step_least(cfg, run["depth"], rows, engine["attn_row_keys"] / steps, live_tokens,
                           engine["moe_experts_hit"] / steps)
    return 100.0 * flops.roofline_seconds(least["flops"], least["bytes"], run["peaks"])["seconds"] / step_busy_s
