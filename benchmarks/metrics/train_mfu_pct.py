"""Model FLOP/s utilisation: the forward+backward matmul and attention FLOPs
a token REQUIRES (``lib/flops.py``; recomputation and the embedding lookup not
counted) times tokens/s of this run's window, over chips times the bf16 peak."""
NAME, UNIT, LAYER, MOVES = "train_mfu_pct", "%", "model", "train_tokens_per_s"


def read(run):
    from lib import flops

    if run["driver"] != "train":
        return None
    per_token = flops.train_flops_per_token(run["cfg"], run["depth"], run["seq"])
    rate = run["e2e"]["train_tokens_per_s"]
    return 100.0 * per_token * rate / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
