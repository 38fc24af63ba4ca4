"""Peak device memory of the run on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``; one process per run, read before the
reference runs). One reader for ``hbm_peak_gb.<split>``: the splits differ only
in the end-to-end metric they move, which ``BENCHMARK.json`` states."""
NAME, UNIT, LAYER = "hbm_peak_gb", "GB", "device"


def read(run):
    if not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / 1e9
