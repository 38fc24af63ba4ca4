"""The trace reduction on a synthetic trace whose answers are known, and on
the small trace recorded on the chip (``benchmarks/testdata/``)."""

import os

import pytest

from lib import xplane

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")


def synthetic():
    return {
        "devices": {
            0: [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.5), ("all-reduce.3", 1.25, 2.0), ("kernel_a", 3.0, 4.0)],
            1: [("fusion.1", 0.0, 2.0), ("all-reduce.3", 2.0, 2.5)],
        },
        "spans": [("bench.outer", 0.0, 5.0), ("bench.inner", 2.0, 3.2)],
        "lines": {},
    }


def test_busy_is_the_union_and_idle_is_named_by_the_innermost_span():
    r = xplane.reduce(synthetic(), window=(0.0, 5.0))
    assert r["per_device"][0]["busy_s"] == pytest.approx(3.0)  # [0,2] and [3,4]
    assert r["per_device"][1]["busy_s"] == pytest.approx(2.5)
    assert r["busy_s"] == pytest.approx(2.75) and r["window_s"] == 5.0
    # device 0 idles 2..3 (midpoint in inner) and 4..5 (outer); device 1 idles 2.5..5 (outer)
    assert r["idle_by_span_s"]["inner"] == pytest.approx(0.5)
    assert r["idle_by_span_s"]["outer"] == pytest.approx((1.0 + 2.5) / 2)
    assert r["longest_gaps"][0] == ("outer", pytest.approx(2.5))


def test_op_time_is_the_mean_over_devices_and_clipped_to_the_window():
    r = xplane.reduce(synthetic(), window=(0.5, 5.0))
    assert r["op_time_s"]["fusion.1"] == pytest.approx((0.5 + 1.5) / 2)
    assert r["op_time_s"]["kernel_a"] == pytest.approx(0.5)
    b = xplane.breakdown(r)
    assert b["device_ops"][0][0] == "fusion.1" and len(b["device_ops"]) <= 10


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}, "spans": [], "lines": {}})


def test_recorded_chip_trace():
    path = os.path.join(TESTDATA, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in benchmarks/testdata yet")
    raw = xplane.load(path)
    assert list(raw["devices"]) == [0] and len(raw["devices"][0]) >= 3
    steps = [s for s in raw["spans"] if s[0] == "bench.step"]
    assert len(steps) == 3
    r = xplane.reduce(raw, window=(steps[0][1], steps[-1][2]))
    assert 0 < r["busy_s"] < r["window_s"]
    # the pauses between steps (10 ms each) are idle time named "pause"
    assert r["idle_by_span_s"].get("pause", 0.0) >= 0.015
    assert r["idle_by_span_s"]["pause"] == pytest.approx(max(r["idle_by_span_s"].values()))


def test_paged_roofline_reader_counts_the_live_kv_of_the_traced_pumps():
    import run as harness

    cfg = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8, "reference": "decoder"}
    kernel = ('%_step_impl.26 = bf16[16,8,64,128]{3,2,1,0} custom-call(bf16[16,8,64,128]{3,2,1,0} %a, '
              's32[16,256]{1,0} %t, bf16[4096,8,16,128]{3,2,1,0} %k, bf16[4096,8,16,128]{3,2,1,0} %v), '
              'custom_call_target="tpu_custom_call"')
    run = {"trace": {"raw": {"devices": {0: [(kernel, 0.0, 0.008), ("fusion.3", 0.008, 0.009)]}, "spans": []}},
           "driver": "serve", "traced_pumps": [(0.0, 0.1, 5000, 300)], "cfg": cfg, "depth": 8,
           "counters": {"max_slots": 16, "max_blocks_per_seq": 256, "num_blocks": 4096, "block_size": 16},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # 5000 live tokens x 8 KV heads x 128 x 2 (k, v) x 2 bytes x 8 layers = 163.84 MB: 0.2 ms at 819 GB/s, of 8 ms
    assert harness.load_reader("paged_attn_roofline").read(run) == pytest.approx(100 * 163.84e6 / 819e9 / 0.008)
    assert harness.load_reader("kv_live_pct").read(dict(run, window_pumps=run["traced_pumps"])) == pytest.approx(100 * 300 / 4096)
    assert harness.load_reader("device_idle_pct.serve").read({"trace": {"reduced": {"busy_s": 3.0, "window_s": 4.0}}}) == 25.0
