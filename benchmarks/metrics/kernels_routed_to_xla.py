"""How many kernel series ran their XLA composition because the trace was
partitioned over devices: the number of non-zero series of
``paddle_tpu_kernel_partition_routed_total`` (0 on one chip)."""
NAME, UNIT, LAYER, MOVES = "kernels_routed_to_xla", "count", "kernel dispatch", "itl_p95_ms"


def read(run):
    return float(sum(1 for v in run["counters"]["routed_to_xla"].values() if v))
