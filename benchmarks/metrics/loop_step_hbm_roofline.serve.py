"""How far a serving step of a looped stack is from "the weights once per
pass, the head, the live KV, and nothing else": the bytes a step has to move
(``reference/looped_decoder.py::step_hbm_bytes``: the layers' matrices times
the passes the engine ran a step, window delta of ``engine.stats
["loop_passes"]`` over that of ``["steps"]``; the head once; every decoding
slot's keys and values once per attention call, as ``paged_attn_roofline``
counts them, at the pool's own bytes a value) at the HBM peak, over the
device-busy time of a step: the union of the device's operations inside the
benchmark's ``frontend.pump`` spans of the traced slice, over their number.
Memory bound by construction at decode. A program without the counter, or a
configuration whose reference file does not count a step's bytes, reads nothing."""
NAME, UNIT, LAYER, MOVES = "loop_step_hbm_roofline.serve", "%", "model", "itl_p95_ms"


def read(run):
    from lib import arch, flops, xplane

    if not run.get("trace") or run["driver"] != "serve" or not run.get("traced_pumps"):
        return None
    cfg, c = run["cfg"], run["counters"]
    engine, count = c.get("engine", {}), getattr(arch.reference(cfg), "step_hbm_bytes", None)
    if count is None or not engine.get("loop_passes") or not engine.get("steps"):
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
    sets = flops.attention_passes(cfg, run["depth"])
    kv_value_bytes = c["pool"]["bytes_per_token"] / (2.0 * sets * cfg["num_key_value_heads"] * flops.head_dim(cfg))
    live = sum(p[2] for p in run["traced_pumps"]) / len(run["traced_pumps"])
    need = count(cfg, run["depth"], engine["loop_passes"] / engine["steps"], live, kv_bytes=kv_value_bytes)
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / step_busy_s
