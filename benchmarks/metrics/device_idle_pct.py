"""Share of the traced slice in which no operation ran on the device: 1 minus
the union of the device-op intervals over the slice, mean over chips. One
reader for ``device_idle_pct.<split>``: the splits differ only in the
end-to-end metric they move, which ``BENCHMARK.json`` states."""
NAME, UNIT, LAYER = "device_idle_pct", "%", "device"


def read(run):
    if not run.get("trace"):
        return None
    r = run["trace"]["reduced"]
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
