"""How far a serving step of the hybrid model is from the least it can move:
``reference/hybrid_ssm_moe.py::step_hbm_bytes`` (every held matrix once, a
routed expert ONLY where the window's ``engine.stats["moe_experts_hit"]`` says
it got a row, each live slot's recurrent state read and written in every
state-space block, each live token's keys and values once per attention
block, the step's embedding rows) at the HBM peak, over the device-busy time of
a step: the union of the device's operations inside the benchmark's
``frontend.pump`` spans of the traced slice, over their number. Live tokens are
the traced pumps' mean (the benchmark's own client side); rows, live slots
(counted from below) and experts hit a step are the window's means of the
program's counters.
It counts the least, so it cannot pass 100. A program without the counters, or
a configuration whose reference file does not count them, reads nothing."""
NAME, UNIT, LAYER, MOVES = "hybrid_step_hbm_roofline.serve", "%", "model", "itl_p95_ms"


def read(run):
    from lib import arch, xplane

    if not run.get("trace") or run["driver"] != "serve" or not run.get("traced_pumps"):
        return None
    cfg, c = run["cfg"], run["counters"]
    engine, ref = c.get("engine", {}), arch.reference(cfg)
    if "moe_experts_hit" not in engine or not engine.get("steps") or not hasattr(ref, "state_bytes_per_slot"):
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
    steps = engine["steps"]
    sets = ref.attention_passes(cfg, run["depth"])
    kv_value_bytes = c["pool"]["bytes_per_token"] / (2.0 * sets * cfg["num_key_value_heads"] * ref.head_dim(cfg))
    traced = run["traced_pumps"]
    kv_live = sum(p[2] for p in traced) / len(traced)
    # rows a step: prompt rows (the program's counter) and one row an output token; the slots that
    # carried them, from below: a decode row is a slot, a prefilling slot has at most a chunk of rows
    prompt_rows, out_rows = engine.get("prompt_tokens_computed", 0) / steps, run["out_tokens_in_window"] / steps
    rows = prompt_rows + out_rows
    slots_live = min(float(c["max_slots"]), out_rows + prompt_rows / c["prefill_chunk"])
    need = ref.step_hbm_bytes(cfg, run["depth"], rows, slots_live, kv_live, engine["moe_experts_hit"] / steps,
                              kv_bytes=kv_value_bytes)
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / step_busy_s
