"""Both drivers, end to end, at a tiny width on the CPU. The harness's look
for a TPU is skipped ONLY by this test's own monkeypatches; run.py has no
option for it."""

import pytest

import run as harness
from lib import device

CELLS = [w["name"] for w in harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(cell, trace, run_cell):
    out, notes = run_cell(cell, seed=2**31 + 11, seconds=1.5, trace=trace)
    checks = [n for n in notes if n.get("note") == "check"]
    assert out["correct"] is True, checks
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    # every number compared stands beside its limit, last in the result's line
    assert list(out)[-1] == "checks" and set(out["checks"]) == {c["name"] for c in checks}
    assert all(set(row) == {"value", "limit", "ok"} and row["ok"] for row in out["checks"].values())
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    if not trace:
        want = {m["name"] for m in bench["end_to_end"] if harness.applies(m, cell)}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
    else:
        assert out["metrics"], "a traced run reports at least one per-layer metric"
        assert set(out["metrics"]) <= {m["name"] for m in bench["per_layer"] if harness.applies(m, cell)}


def test_refuses_without_tpu(capsys):
    with pytest.raises(device.NoAccelerator):
        harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert not any('"metrics"' in l for l in lines)
