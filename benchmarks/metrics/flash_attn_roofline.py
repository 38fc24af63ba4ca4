"""Flash attention's share of its roofline over the traced steps: the least
time the chip could take for the calls seen (``lib/flops.py``: causal FLOPs at
the bf16 peak against q/k/v/o bytes at the HBM peak, whichever is larger; a
backward call is dq plus dk/dv, recomputation not counted) over the device time
of the kernels' events.

A Pallas kernel shows in the trace as a ``custom-call`` with the target
``tpu_custom_call`` and carries no name of its own, so the three kernels are
told by their result shapes at this cell's sizes ([batch, heads, seq, head_dim]):
forward ``(bf16[..], f32[B,H,S,1])`` (output and log-sum-exp), dq ``bf16[..]``,
dk/dv ``(f32[..], f32[..])``."""
NAME, UNIT, LAYER, MOVES = "flash_attn_roofline", "%", "Pallas kernels", "train_tokens_per_s"


def read(run):
    from lib import flops, xplane

    if not run.get("trace") or run["driver"] != "train":
        return None
    cfg = run["cfg"]
    nh = cfg["num_attention_heads"]
    bhsd = f"[{run['batch']},{nh},{run['seq']},{flops.head_dim(cfg)}]"
    lse = f"f32[{run['batch']},{nh},{run['seq']},1]"
    fwd, dq, dkv = [], [], []
    for name, a, b in xplane.pallas_events(run["trace"]["raw"]):
        out = xplane.result_shapes(name)
        if out.startswith(f"(bf16{bhsd}") and lse in out:
            fwd.append(b - a)
        elif out == f"bf16{bhsd}":
            dq.append(b - a)
        elif out.startswith(f"(f32{bhsd}, f32{bhsd}"):
            dkv.append(b - a)
    if not fwd and not dq and not dkv:
        return None
    least = 0.0
    for backward, calls in ((False, len(fwd)), (True, max(len(dq), len(dkv)))):
        cost = flops.flash_attention_cost(cfg, run["batch"], run["seq"], backward)
        least += calls * flops.roofline_seconds(cost["flops"], cost["bytes"], run["peaks"])["seconds"]
    return 100.0 * least / (sum(fwd) + sum(dq) + sum(dkv))
