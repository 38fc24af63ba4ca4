"""The readers of PR 38's per-layer metrics (``lib/seam.py``): on a synthetic
trace whose gaps are known, under a shift of the device's clock, on a program
without the sub-phases, and on the third small trace recorded on the chip
(``benchmarks/testdata/seam.xplane.pb``, by ``tests/record_seam_trace.py``)."""

import json
import os

import pytest

import run as harness
from lib import phases, seam, xplane

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
RECORDED = os.path.join(TESTDATA, "seam.xplane.pb")
CELLS = ["mistral7b.serve_chat", "ouro2.6b.serve_reason", "nemotron3nano.serve_chat", "deepseekv2.serve_doc"]
COUNTER_METRICS = {"host_put_ms.serve": "subphase_s.launch_put", "host_args_ms.serve": "subphase_s.launch_args",
                   "host_call_ms.serve": "subphase_s.launch_call", "host_fetch_ms.serve": "subphase_s.wait_fetch"}
TRACE_METRICS = ("seam_idle_ms.serve", "call_to_first_op_ms.serve", "last_op_to_wake_ms.serve",
                 "trace_clock_slack_ms.serve")
NEW = tuple(COUNTER_METRICS) + TRACE_METRICS

MLP = "%fusion.9 = bf16[16,16,14336]{2,1,0} fusion(bf16[16,16,4096]{2,1,0} %h), kind=kOutput"
COPY = "%copy.3 = bf16[4096,8,16,128]{3,2,0,1} copy(bf16[4096,8,16,128]{3,2,1,0} %k)"
STEP_S = 0.010
# per step, ms after the step's launch starts: the call starts at 0.8; the device's first
# operation starts FIRST ms after the call, its last ends LAST ms before the host knows
FIRST = (0.55, 0.7, 0.4, 0.9, 0.6, 0.5, 0.85)
LAST = (0.30, 0.20, 0.50, 0.25, 0.45, 0.30, 0.20)
INNER = 0.1  # ms of idle between the step's two operations: inside D, part of neither end


def synthetic(steps=7, shift_s=0.0, subphases=True, cut=(), stall=None):
    """``steps`` steps of 10 ms whose host phases tile the pump. Step k's
    device operations run from ``call + FIRST[k]`` to ``ready - LAST[k]`` with
    one gap of INNER between them; ``shift_s`` moves every device event (a
    device clock ahead of or behind the host's); ``cut`` names steps whose
    sub-phase spans are missing (cut by the profile's start); ``stall`` is
    ``(step, where, seconds)``: that step's ``plan`` or ``put`` takes so much
    longer (the host, the device idle meanwhile), or the device stands still
    so long between the step's two operations (``pause``: halfway through;
    ``late_pause``: with a seventh of the work left, so that the pause ends
    nearer the next call than that call's put is long), or the host learns so
    much later that the result is ready (``wake``)."""
    ops, spans = [], []
    t = 0.0
    for k in range(steps):
        extra = {where: stall[2] if stall and stall[:2] == (k, where) else 0.0 for where in ("plan", "put", "pause", "late_pause", "wake")}
        pause, share = extra["pause"] + extra["late_pause"], 0.85 if extra["late_pause"] else 0.5
        launch = t + 0.0010 + extra["plan"]
        put_end = launch + 0.0007 + extra["put"]
        call, call_end = put_end + 0.0001, put_end + 0.0003
        ready = call + 0.0070 + pause + extra["wake"]
        wait_end, commit_end, end = ready + 0.0002, ready + 0.0008, ready + 0.0012
        spans += [("paddle_tpu.frontend.pump", t, end), ("paddle_tpu.frontend.deliver", t, t + 0.0002),
                  ("paddle_tpu.engine.decode_step", t + 0.0002, commit_end),
                  ("paddle_tpu.engine.plan", t + 0.0002, launch), ("paddle_tpu.engine.launch", launch, call_end),
                  ("paddle_tpu.engine.wait", call_end, wait_end), ("paddle_tpu.engine.commit", wait_end, commit_end),
                  ("paddle_tpu.frontend.deliver", commit_end, end)]
        if subphases and k not in cut:
            spans += [("paddle_tpu.engine.launch.put", launch, put_end), ("paddle_tpu.engine.launch.args", put_end, call),
                      ("paddle_tpu.engine.launch.call", call, call_end), ("paddle_tpu.engine.wait.ready", call_end, ready),
                      ("paddle_tpu.engine.wait.fetch", ready, wait_end)]
        first_op, last_op = call + 1e-3 * FIRST[k] + shift_s, ready - extra["wake"] - 1e-3 * LAST[k] + shift_s
        middle = first_op + share * (last_op - pause - first_op)
        ops += [(MLP, first_op, middle, 0), (COPY, middle + 1e-3 * INNER + pause, last_op, 0)]
        t = end
    return {"trace": {"raw": {}}, "_program_trace": {"scopes": {}, "spans": sorted(spans, key=lambda s: s[1]),
                                                    "window": (0.0, t), "ops": ops}}


def read(name, run):
    return harness.load_reader(name).read(run)


def test_the_reader_gives_the_known_gaps_of_the_whole_steps():
    run = synthetic()
    got = seam.steps(run)
    assert len(got) == 5  # seven steps less the slice's first and last
    for step, first, last in zip(got, FIRST[1:6], LAST[1:6]):
        assert step["first"] == pytest.approx(1e-3 * first, abs=1e-12)
        assert step["last"] == pytest.approx(1e-3 * last, abs=1e-12)
        assert step["H"] == pytest.approx(0.0070, abs=1e-12)
        assert step["H"] - step["D"] == pytest.approx(1e-3 * (first + last), abs=1e-12)
        assert step["first"] + step["last"] == pytest.approx(step["H"] - step["D"], abs=1e-15)
        assert step["inner"] == pytest.approx(1e-3 * INNER, abs=1e-12)  # inside D: in neither end
    sums = sorted(f + l for f, l in zip(FIRST[1:6], LAST[1:6]))
    assert read("seam_idle_ms.serve", run) == pytest.approx(sums[2])
    assert read("call_to_first_op_ms.serve", run) == pytest.approx(sorted(FIRST[1:6])[2])
    assert read("last_op_to_wake_ms.serve", run) == pytest.approx(sorted(LAST[1:6])[2])
    assert read("trace_clock_slack_ms.serve", run) == pytest.approx(min(FIRST[1:6]) + min(LAST[1:6]))
    # the old reader starts at the launch's start, 0.8 ms before the call: the same first operations
    assert read("launch_to_first_op_ms.serve", run) == pytest.approx(0.8 + sorted(FIRST)[3])


@pytest.mark.parametrize("shift_ms", [1.0, -1.0])
def test_a_shift_of_the_devices_clock_moves_the_parts_and_not_the_whole(shift_ms):
    still, moved = synthetic(), synthetic(shift_s=1e-3 * shift_ms)
    assert read("seam_idle_ms.serve", moved) == pytest.approx(read("seam_idle_ms.serve", still), abs=1e-9)
    assert read("trace_clock_slack_ms.serve", moved) == pytest.approx(read("trace_clock_slack_ms.serve", still), abs=1e-9)
    assert read("call_to_first_op_ms.serve", moved) == pytest.approx(read("call_to_first_op_ms.serve", still) + shift_ms, abs=1e-9)
    assert read("last_op_to_wake_ms.serve", moved) == pytest.approx(read("last_op_to_wake_ms.serve", still) - shift_ms, abs=1e-9)
    for a, b in zip(seam.steps(still), seam.steps(moved)):
        assert b["H"] - b["D"] == pytest.approx(a["H"] - a["D"], abs=1e-12)
    # a device clock a millisecond behind puts the first operation BEFORE its call: one part
    # reads below zero, which only the slack (a sum of the two least parts) does not mind
    if shift_ms < 0:
        assert read("call_to_first_op_ms.serve", moved) < 0 < read("trace_clock_slack_ms.serve", moved)


def test_a_trace_that_breaks_causality_reads_a_negative_slack():
    run = synthetic()
    trace = run["_program_trace"]
    # step 3's last operation ends 0.6 ms AFTER the host saw the result ready
    k = 3
    late = STEP_S * k + 0.0088 + 0.0006
    trace["ops"] = [(n, a, late if n == COPY and STEP_S * k < a < STEP_S * (k + 1) else b, d) for n, a, b, d in trace["ops"]]
    assert read("trace_clock_slack_ms.serve", run) == pytest.approx(min(FIRST[1:6]) - 0.6)


@pytest.mark.parametrize("cut,kept", [((1,), [2, 3, 4, 5]), ((5,), [1, 2, 3]), ((1, 5), [2, 3]), ((3,), [1, 4, 5])])
def test_a_step_the_profile_cut_is_left_out(cut, kept):
    # with the step before it, whose last operation ends where the cut step's first would begin
    got = seam.steps(synthetic(cut=cut))
    assert [s["first"] for s in got] == pytest.approx([1e-3 * FIRST[k] for k in kept], abs=1e-12)
    assert [s["last"] for s in got] == pytest.approx([1e-3 * LAST[k] for k in kept], abs=1e-12)
    # and the slice's own first and last steps never count, whole or not
    whole = [s["first"] for s in seam.steps(synthetic())]
    assert whole == pytest.approx([1e-3 * f for f in FIRST[1:6]], abs=1e-12)


# a stall of 30 ms (three steps' time; what lib/phases.py's search from the launch takes for "near" is 5 ms here)
@pytest.mark.parametrize("where", ["plan", "put", "pause", "late_pause", "wake"])
@pytest.mark.parametrize("at", [2, 3, 4])
def test_a_stalled_step_moves_no_other_steps_reading(at, where):
    run = synthetic(stall=(at, where, 0.030))
    got = seam.steps(run)
    assert len(got) == 5
    for k, step in zip(range(1, 6), got):
        pause, wake = ((0.030 if k == at and where.endswith(w) else 0.0) for w in ("pause", "wake"))
        assert step["first"] == pytest.approx(1e-3 * FIRST[k], abs=1e-12)
        assert step["last"] == pytest.approx(1e-3 * LAST[k] + wake, abs=1e-12)
        assert step["H"] == pytest.approx(0.0070 + pause + wake, abs=1e-12)
        assert step["inner"] == pytest.approx(1e-3 * INNER + pause, abs=1e-12)  # the device's pause is inside D
        assert step["first"] + step["last"] == pytest.approx(step["H"] - step["D"], abs=1e-12)
    # the slack and the near end are the unstalled trace's; so are the whole and the far end,
    # but for the step whose wake came late (one value of five, which the median here feels)
    for name in TRACE_METRICS if where != "wake" else ("call_to_first_op_ms.serve", "trace_clock_slack_ms.serve"):
        assert read(name, run) == pytest.approx(read(name, synthetic()), abs=1e-9), name


def test_counter_readers_take_the_windows_delta():
    engine = {"steps": 500, "phase_s.plan": 1.5, "phase_s.launch": 1.0, "phase_s.wait": 42.0,
              "subphase_s.launch_put": 0.35, "subphase_s.launch_args": 0.025, "subphase_s.launch_call": 0.625,
              "subphase_s.wait_ready": 41.95, "subphase_s.wait_fetch": 0.05}
    run = {"counters": {"engine": engine}}
    want = {"host_put_ms.serve": 0.7, "host_args_ms.serve": 0.05, "host_call_ms.serve": 1.25, "host_fetch_ms.serve": 0.1}
    for name, value in want.items():
        assert read(name, run) == pytest.approx(value), name
    # the three launch parts are the launch's own per-step time
    assert sum(want[n] for n in list(want)[:3]) == pytest.approx(phases.per_step_ms(run, "phase_s.launch"))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_subphases_reads_none(name):
    # the parent of PR 38: phase counters and phase spans, no sub-phase of either kind
    engine = {"steps": 500, "phase_s.plan": 1.5, "phase_s.launch": 0.5, "phase_s.wait": 42.0, "phase_s.commit": 0.25,
              "phase_s.deliver": 0.2, "stall_s.host": 0.0, "stall_s.device": 0.0, "stall_steps": 0}
    parent = dict(synthetic(subphases=False), counters={"engine": engine})
    assert read(name, parent) is None
    assert read("launch_to_first_op_ms.serve", parent) is not None  # the old reader still reads it
    # and a program with no phase at all, traced or not (the parent of PR 23)
    assert read(name, {"counters": {"engine": {"steps": 500, "recoveries": 0}}, "trace": None}) is None
    bare = synthetic(subphases=False)
    bare["_program_trace"]["spans"] = []
    assert read(name, dict(bare, counters={"engine": {"steps": 500}})) is None


def test_the_entries_are_appended_for_the_four_serving_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in entries] == list(NEW)
    serving = [w["name"] for w in bench["workloads"] if "serve" in w["traffic"]]
    assert serving == CELLS
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == ("ms", "lower", "serving host", "itl_p95_ms")
        assert m["workloads"] == CELLS
        assert m["source"] == ("program_counter" if m["name"] in COUNTER_METRICS else "device_trace")
        reader = harness.load_reader(m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (m["name"], m["unit"], m["layer"], m["moves"])


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded seam trace in benchmarks/testdata yet")
    with open(os.path.join(TESTDATA, "seam.json")) as fh:
        facts = json.load(fh)
    return {"trace": {"raw": xplane.load(RECORDED)}, "xplane_path": RECORDED, "driver": "serve",
            "counters": {"engine": facts["engine"]}, "facts": facts}


def test_recorded_trace_is_small_and_holds_every_subphase_nested_in_its_parent(recorded):
    assert os.path.getsize(RECORDED) < 600_000
    pumps = recorded["facts"]["pumps"]
    children = {"engine.launch": seam.SUBPHASES[:3], "engine.wait": seam.SUBPHASES[3:]}
    for parent, names in children.items():
        parents = phases.spans_named(recorded, parent)
        assert len(parents) == pumps
        for name in names:
            assert len(phases.spans_named(recorded, name)) == pumps, name
        for k, (_n, a, b) in enumerate(parents):
            kids = [phases.spans_named(recorded, name)[k] for name in names]
            assert a <= kids[0][1] and kids[-1][2] <= b
            for left, right in zip(kids, kids[1:]):  # in order, the next after the last
                assert left[2] <= right[1]
            # on the trace's clock an annotation's end and the next one's start lie some
            # 20 us apart while a profile is taken (test_phases.py): the children cover
            # their parent but for that
            assert sum(s[2] - s[1] for s in kids) >= (b - a) - 60e-6 * (len(kids) + 1)


def test_recorded_trace_readers_give_what_they_gave_on_the_day(recorded):
    facts = recorded["facts"]
    got = seam.steps(recorded)
    assert len(got) == facts["pumps"] - 2 == len(facts["steps"])
    for step, pinned in zip(got, facts["steps"]):
        assert step == pytest.approx(pinned, rel=1e-9, abs=1e-12)
        assert step["first"] + step["last"] == pytest.approx(step["H"] - step["D"], abs=1e-12)
        assert 0 < step["D"] < step["H"]
    readings = facts["readings"]
    assert read("seam_idle_ms.serve", recorded) == pytest.approx(readings["seam_idle_ms"], rel=1e-9)
    assert read("call_to_first_op_ms.serve", recorded) == pytest.approx(readings["call_to_first_op_ms"], rel=1e-9)
    assert read("last_op_to_wake_ms.serve", recorded) == pytest.approx(readings["last_op_to_wake_ms"], rel=1e-9)
    assert read("trace_clock_slack_ms.serve", recorded) == pytest.approx(readings["trace_clock_slack_ms"], rel=1e-9)
    assert phases.launch_to_first_op_ms(recorded) == pytest.approx(readings["launch_to_first_op_ms"], rel=1e-9)
    assert read("trace_clock_slack_ms.serve", recorded) >= 0  # the recorded trace keeps causality
    # the counters tile their parents over the traced pumps
    engine = facts["engine"]
    assert sum(engine[k] for k in ("subphase_s.launch_put", "subphase_s.launch_args", "subphase_s.launch_call")) \
        == pytest.approx(engine["phase_s.launch"], rel=1e-9)
    assert engine["subphase_s.wait_ready"] + engine["subphase_s.wait_fetch"] == pytest.approx(engine["phase_s.wait"], rel=1e-9)
    for name, key in COUNTER_METRICS.items():
        assert read(name, recorded) == pytest.approx(1e3 * engine[key] / engine["steps"])
