"""The readers of PR 23's per-layer metrics: on a synthetic run whose answers
are known, and on the second small trace recorded on the chip
(``benchmarks/testdata/phases.xplane.pb``, by ``tests/record_phases_trace.py``)."""

import json
import os

import pytest

import run as harness
from lib import phases, xplane, xspace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
RECORDED = os.path.join(TESTDATA, "phases.xplane.pb")

PAGED = ('%paged_attention_chunk.26 = bf16[16,8,64,128]{3,2,1,0} custom-call(bf16[16,8,64,128]{3,2,1,0} %a), '
         'custom_call_target="tpu_custom_call"')
UNNAMED = ('%_step_impl.26 = bf16[16,8,64,128]{3,2,1,0} custom-call(bf16[16,8,64,128]{3,2,1,0} %a), '
           'custom_call_target="tpu_custom_call"')
COND = "%cond.57 = bf16[4096,8,16,128]{3,2,1,0} conditional(pred[] %p, bf16[4096,8,16,128]{3,2,1,0} %k)"
COPY = "%copy.3 = bf16[4096,8,16,128]{3,2,0,1} copy(bf16[4096,8,16,128]{3,2,1,0} %k)"
MLP = "%fusion.9 = bf16[16,16,14336]{2,1,0} fusion(bf16[16,16,4096]{2,1,0} %h), kind=kOutput"
ADAM = "%fusion.232 = (bf16[4096,14336]{1,0}, f32[4096,14336]{1,0}) fusion(f32[4096,14336]{1,0} %m), kind=kLoop"


def synthetic():
    """Two steps of 10 ms; the device starts 0.5 ms after each launch starts
    and the host's phases tile each pump."""
    ops, spans = [], []
    for k in range(2):
        t = 0.010 * k
        spans += [("paddle_tpu.frontend.pump", t, t + 0.010), ("paddle_tpu.frontend.deliver", t, t + 0.0002),
                  ("paddle_tpu.engine.decode_step", t + 0.0002, t + 0.0096),
                  ("paddle_tpu.engine.plan", t + 0.0002, t + 0.0010), ("paddle_tpu.engine.launch", t + 0.0010, t + 0.0020),
                  ("paddle_tpu.engine.wait", t + 0.0020, t + 0.0090), ("paddle_tpu.engine.commit", t + 0.0090, t + 0.0096),
                  ("paddle_tpu.frontend.deliver", t + 0.0096, t + 0.010)]
        ops += [(PAGED, t + 0.0015, t + 0.0055, 0), (COND, t + 0.0055, t + 0.0065, 0), (COPY, t + 0.0065, t + 0.0070, 0),
                (MLP, t + 0.0070, t + 0.0085, 0), (UNNAMED, t + 0.0085, t + 0.0090, 0)]
    scopes = {COND: "jit(_step_impl)/kv_cow/cond:", COPY: "jit(_step_impl)/attention/kv_cache_update/scatter:",
              MLP: "jit(_step_impl)/mlp/dot_general:", PAGED: "jit(_step_impl)/attention/pallas_call:"}
    return {"trace": {"raw": {}}, "_program_trace": {"scopes": scopes, "spans": sorted(spans, key=lambda s: s[1]),
                                                    "window": (0.0, 0.020), "ops": ops}}


def test_a_kernel_is_found_by_its_name_and_an_operation_by_its_innermost_scope():
    run = synthetic()
    trace = run["_program_trace"]
    assert phases.kernel_of(PAGED) == "paged_attention_chunk" and phases.kernel_of(UNNAMED) == "_step_impl"
    assert phases.kernel_of(COND) is None  # not a Pallas kernel
    assert phases.scope_of(trace, COPY) == "kv_cache_update" and phases.scope_of(trace, COND) == "kv_cow"
    assert phases.scope_of(trace, UNNAMED) is None
    assert phases.scope_of({"scopes": {ADAM: "jit(staged)/jit(fused)/optimizer_update/mul:"}}, ADAM) == "optimizer_update"
    assert phases.scope_of({"scopes": {MLP: "jit(staged)/mlp/transpose(jvp())/dot_general:"}}, MLP) == "mlp"
    # 15 ms of device time in all: paged 8, pool 3, named all but the 1 ms of the nameless kernel
    assert harness.load_reader("paged_attn_pct.serve").read(run) == pytest.approx(100 * 8 / 15)
    assert harness.load_reader("kv_pool_copy_pct.serve").read(run) == pytest.approx(100 * 3 / 15)
    assert phases.named_share_pct(run) == pytest.approx(100 * 14 / 15)
    for absent in ("loss_head_pct.train", "attention_pct.train", "optimizer_pct.train"):
        assert harness.load_reader(absent).read(run) is None
    # a conditional has no scope of its own and ENCLOSES its branch's operations: shares
    # are unions of intervals, so the time counts once and the branch's scope names it
    nested = synthetic()
    trace = nested["_program_trace"]
    del trace["scopes"][COND]
    inner = "%copy.80 = bf16[4096,8,16,128]{3,1,2,0} copy(bf16[4096,8,16,128]{3,2,1,0} %gte)"
    trace["scopes"][inner] = "jit(_step_impl)/kv_cow/cond:"
    trace["ops"] += [(inner, a + 0.0001, b, d) for n, a, b, d in trace["ops"] if n == COND]
    # two steps: 2 x (0.9 ms of the branch + 0.5 ms of the append's copy) of 15 ms busy
    assert harness.load_reader("kv_pool_copy_pct.serve").read(nested) == pytest.approx(100 * 2 * (0.9 + 0.5) / 15)
    assert phases.named_share_pct(nested) == pytest.approx(100 * (14 - 2 * 0.1) / 15)
    # a train step's kernels are named through the transform they were traced under
    wrapped = PAGED.replace("%paged_attention_chunk.26", "%jvp_fused_loss_dw_.1")
    assert phases.in_family(wrapped, phases.LOSS_KERNELS) and not phases.in_family(wrapped, phases.FLASH_KERNELS)
    assert not phases.in_family(UNNAMED, *phases.KERNEL_FAMILIES) and not phases.in_family(COND, *phases.KERNEL_FAMILIES)


def test_launch_to_first_op_and_gaps_by_phase():
    run = synthetic()
    assert harness.load_reader("launch_to_first_op_ms.serve").read(run) == pytest.approx(0.5)
    idle = phases.idle_by_phase_s(run)
    # each step: idle 0..1.5 ms (midpoint 0.75: plan) and 9..10 ms (midpoint 9.5: commit);
    # the second step's first gap runs from 9 ms of the first step to 11.5 ms (midpoint 10.25: plan)
    assert idle == pytest.approx({"engine.plan": 0.0015 + 0.0025, "engine.commit": 0.001})
    assert phases.phase_at(run["_program_trace"], 0.0098) == "frontend.deliver"
    assert phases.phase_at(run["_program_trace"], 0.5) == "outside"
    # a device clock that runs ahead of the host's: the difference is reported as it is
    early = synthetic()
    early["_program_trace"]["ops"] = [(n, a - 0.0008, b - 0.0008, d) for n, a, b, d in early["_program_trace"]["ops"]]
    assert phases.launch_to_first_op_ms(early) == pytest.approx(-0.3)


def test_counter_readers_take_the_windows_delta_and_a_parent_reads_nothing():
    engine = {"steps": 500, "phase_s.plan": 1.5, "phase_s.launch": 0.5, "phase_s.wait": 42.0, "phase_s.commit": 0.25,
              "phase_s.deliver": 0.2, "stall_s.host": 2.06, "stall_s.device": 0.0, "stall_steps": 1}
    run = {"counters": {"engine": engine}, "_phases_noted": True}
    want = {"host_plan_ms.serve": 3.0, "host_launch_ms.serve": 1.0, "device_wait_ms.serve": 84.0,
            "host_commit_ms.serve": 0.5, "host_deliver_ms.serve": 0.4, "stall_host_ms.serve": 2060.0,
            "stall_device_ms.serve": 0.0}
    for name, value in want.items():
        assert harness.load_reader(name).read(run) == pytest.approx(value), name
    parent = {"counters": {"engine": {"steps": 500, "recoveries": 0}}, "trace": None, "_phases_noted": True}
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    new = [m["name"] for m in bench["per_layer"][13:]]
    assert len(new) >= 13
    for name in new:
        assert harness.load_reader(name).read(parent) is None, name


def test_new_entries_name_one_cell_a_known_layer_and_a_metric_it_reports():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    old, new = bench["per_layer"][:13], bench["per_layer"][13:]
    layers = {m["layer"] for m in old}
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["layer"] in layers and len(m["workloads"]) == 1
        assert m["workloads"][0] in e2e[m["moves"]]
        reader = harness.load_reader(m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (m["name"], m["unit"], m["layer"], m["moves"])


def test_xspace_reads_the_first_recorded_trace_like_profiledata():
    got = xspace.read(os.path.join(TESTDATA, "small.xplane.pb"), "bench.")
    raw = xplane.load(os.path.join(TESTDATA, "small.xplane.pb"))
    assert [(n, pytest.approx(a), pytest.approx(b)) for n, a, b in raw["spans"]] == got["spans"]
    assert set(got["scopes"].values()) == {"jit(<lambda>)/dot_general:"}
    assert all(name in {e[0] for e in raw["devices"][0]} for name in got["scopes"])


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded phases trace in benchmarks/testdata yet")
    with open(os.path.join(TESTDATA, "phases.json")) as fh:
        facts = json.load(fh)
    raw = xplane.load(RECORDED)
    spans = raw["spans"]
    reduced = xplane.reduce(raw, (spans[0][1], max(s[2] for s in spans)))
    return {"trace": {"raw": raw, "reduced": reduced}, "xplane_path": RECORDED, "driver": "serve",
            "counters": {"engine": facts["engine"]}, "_phases_noted": True, "facts": facts}


def test_recorded_trace_holds_every_phase_of_every_pump_nested_and_tiling(recorded):
    pumps = [s for s in recorded["trace"]["raw"]["spans"] if s[0] == "bench.frontend.pump"]
    assert len(pumps) == recorded["facts"]["pumps"]
    for name in phases.PHASES:
        n = 2 * len(pumps) if name == "frontend.deliver" else len(pumps)
        assert len(phases.spans_named(recorded, name)) == n, name
    tile = ("engine.plan", "engine.launch", "engine.wait", "engine.commit", "frontend.deliver")
    for (_n, a, b), inner in zip(pumps, phases.spans_named(recorded, "frontend.pump")):
        assert a <= inner[1] and inner[2] <= b  # the program's pump inside the benchmark's span
        parts = [s for name in tile for s in phases.spans_named(recorded, name) if inner[1] <= s[1] and s[2] <= inner[2]]
        assert len(parts) == 6
        # on the trace's clock the annotations leave the time between one's end and the
        # next one's start uncovered (some 20 us each while a profile is taken): 3 % of
        # this small engine's 4.6 ms pump, 0.2 % of a pump of the chat cell
        total = sum(s[2] - s[1] for s in parts)
        assert 0.94 * (inner[2] - inner[1]) <= total <= inner[2] - inner[1]
    assert not recorded["facts"]["fallbacks"]


def test_recorded_trace_readers(recorded):
    value = {name: harness.load_reader(name).read(recorded) for name in
             ("paged_attn_pct.serve", "kv_pool_copy_pct.serve", "loss_head_pct.train", "attention_pct.train",
              "optimizer_pct.train", "launch_to_first_op_ms.serve", "host_plan_ms.serve", "device_wait_ms.serve")}
    assert all(v is not None for v in value.values()), value
    for share in ("paged_attn_pct.serve", "kv_pool_copy_pct.serve", "loss_head_pct.train", "attention_pct.train",
                  "optimizer_pct.train"):
        assert 0 < value[share] < 100
    trace = phases.program_trace(recorded)
    kernels = {phases.kernel_of(n) for n, *_ in trace["ops"]} - {None}
    # the serving step's kernels carry their names as given; the train step's are traced
    # under jax.vjp, which wraps the name: jvp(<name>), sanitised
    assert {"paged_attention_chunk_fused", "embed_rms_norm", "rms_norm_residual_fwd"} <= kernels
    for name in ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
                 "fused_loss_fwd", "fused_loss_dx", "fused_loss_dw"):
        assert f"jvp_{name}_" in kernels, name
    assert not any(k.startswith("_step_impl") or k.startswith("jvp__") for k in kernels)  # none nameless
    scopes = {phases.scope_of(trace, n) for n, *_ in trace["ops"]} - {None}
    assert {"attention", "mlp", "norm", "kv_cow", "kv_cache_update", "optimizer_update"} <= scopes
    # at this size the unnamed parameter prefetches (copy-start/done) are a sixth of the busy time
    assert phases.named_share_pct(recorded) > 75
    assert abs(value["launch_to_first_op_ms.serve"]) < 5
    # the pauses between the pumps (3 ms each) fall outside every program phase, and the
    # wait for the device's answer is idle time named by the program's own phase
    idle = phases.idle_by_phase_s(recorded)
    assert idle["outside"] >= 0.003 * (recorded["facts"]["pumps"] - 1)
    assert set(idle) <= {"outside", *phases.PHASES}
    # the benchmark's own reduction names the same gaps by its own spans only
    assert set(recorded["trace"]["reduced"]["idle_by_span_s"]) <= {"frontend.pump", "pause", "train.step", "outside_spans"}
