"""The paged chunk kernel's share of its roofline over the traced slice: the
KV bytes the live lengths need (every decoding slot's keys and values once per
attention call per step, ``lib/flops.py``) at the HBM peak, over the device time of the
kernel's events. Memory bound by construction at decode.

A Pallas kernel shows in the trace as a ``custom-call`` with the target
``tpu_custom_call`` and no name of its own: the kernel is told by its operands,
the block tables ``s32[slots, blocks_per_seq]`` and the KV pool
``[num_blocks, kv_heads, block, head_dim]``. Slots still in prefill are left
out of the bytes needed (their progress is not visible from outside the
program), so the share reads lower for them, never higher."""
NAME, UNIT, LAYER, MOVES = "paged_attn_roofline", "%", "Pallas kernels", "itl_p95_ms"


def read(run):
    from lib import flops, xplane

    if not run.get("trace") or run["driver"] != "serve" or not run.get("traced_pumps"):
        return None
    cfg, c = run["cfg"], run["counters"]
    hd, passes = flops.head_dim(cfg), flops.attention_passes(cfg, run["depth"])
    tables = f"s32[{c['max_slots']},{c['max_blocks_per_seq']}]"
    pool = f"[{c['num_blocks']},{cfg['num_key_value_heads']},{c['block_size']},{hd}]"
    seconds = 0.0
    for name, a, b in xplane.pallas_events(run["trace"]["raw"]):
        operands = xplane.operand_shapes(name)
        if tables in operands and pool in operands:
            seconds += b - a
    if not seconds:
        return None
    need = sum(passes * flops.paged_attention_bytes(cfg, pump[2]) for pump in run["traced_pumps"])
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / seconds
