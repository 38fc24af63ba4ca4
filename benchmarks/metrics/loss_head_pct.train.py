"""Share of the traced steps' device-operation time in the fused loss head's
kernels (forward, dX, dW), found by their ``pallas_call`` name
(``fused_loss_*``)."""
NAME, UNIT, LAYER, MOVES = "loss_head_pct.train", "%", "Pallas kernels", "train_tokens_per_s"


def read(run):
    from lib import phases

    phases.note(run)  # once a run: what of the slice's device time has a name
    return phases.kernel_share_pct(run, phases.LOSS_KERNELS)
