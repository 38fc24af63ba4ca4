"""Static-analysis framework tests: per-checker fixtures (positive AND
negative per code), suppression semantics, reporters, CLI exit codes, and the
tier-1 gate — the whole-package self-run must come back with zero
unsuppressed violations."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from paddle_tpu.analysis import (
    all_checkers,
    all_codes,
    analyze_paths,
    analyze_source,
    render_json,
    render_text,
    summarize,
)

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "paddle_tpu"


def codes(src, **kw):
    return sorted(v.code for v in analyze_source(src, **kw) if not v.suppressed)


# -- TS: trace-safety --------------------------------------------------------

def test_ts101_print_in_jitted_function():
    assert "TS101" in codes(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print(x)\n"
        "    return x\n"
    )


def test_ts101_negative_print_outside_trace():
    assert codes("def f(x):\n    print(x)\n    return x\n") == []


def test_ts101_function_passed_to_jax_jit():
    assert "TS101" in codes(
        "import jax\n"
        "def g(x):\n"
        "    print(x)\n"
        "    return x\n"
        "h = jax.jit(g, donate_argnums=(0,))\n"
    )


def test_ts101_method_passed_to_jax_jit_via_self():
    assert "TS101" in codes(
        "import jax\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._fn = jax.jit(self._impl)\n"
        "    def _impl(self, x):\n"
        "        print(x)\n"
        "        return x\n"
    )


def test_ts_shard_map_body_is_traced():
    """The tensor-parallel collective seam: a function handed to shard_map
    (the per-shard kernel wrapper in the engine step path) is a traced body
    — flag reads / metrics / prints inside it fire per compile of the
    partitioned program, multiplied across the mesh."""
    assert "TS104" in codes(
        "from jax.experimental.shard_map import shard_map\n"
        "from paddle_tpu.observability import GLOBAL_METRICS\n"
        "def local_step(x):\n"
        "    GLOBAL_METRICS.counter('c').inc()\n"
        "    return x\n"
        "f = shard_map(local_step, mesh, in_specs=(), out_specs=())\n"
    )
    assert "TS101" in codes(
        "import jax\n"
        "def local_step(x):\n"
        "    print(x)\n"
        "    return x\n"
        "f = jax.experimental.shard_map.shard_map(local_step, mesh,\n"
        "                                         in_specs=(), out_specs=())\n"
    )
    # the modern spelling the repo itself prefers (conftest installs it)
    assert "TS101" in codes(
        "import jax\n"
        "def local_step(x):\n"
        "    print(x)\n"
        "    return x\n"
        "f = jax.shard_map(local_step, mesh=None, in_specs=(), out_specs=())\n"
    )


def test_ts_shard_map_negative_clean_body():
    # a clean per-shard body (the block_attention wrapper's shape) is fine,
    # and host code AROUND the shard_map call may do host things
    assert codes(
        "from jax.experimental.shard_map import shard_map\n"
        "def local_step(x):\n"
        "    return x * 2\n"
        "def dispatch(mesh, x):\n"
        "    print('host side is fine')\n"
        "    return shard_map(local_step, mesh, in_specs=(), out_specs=())(x)\n"
    ) == []


def test_ts_pjit_body_is_traced():
    assert "TS103" in codes(
        "import os\n"
        "from jax.experimental.pjit import pjit\n"
        "def step(x):\n"
        "    if os.environ.get('DEBUG'):\n"
        "        return x\n"
        "    return x + 1\n"
        "f = pjit(step)\n"
    )


def test_ts102_time_call():
    src = (
        "import time\n"
        "from paddle_tpu.jit import to_static\n"
        "@to_static\n"
        "def step(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return x, t0\n"
    )
    assert "TS102" in codes(src)
    assert codes(src.replace("time.perf_counter()", "x + 1")) == []


def test_ts103_environ():
    assert "TS103" in codes(
        "import jax, os\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if os.environ.get('DEBUG'):\n"
        "        return x\n"
        "    return x + 1\n"
    )
    # reading the environment OUTSIDE the traced body is fine
    assert codes(
        "import jax, os\n"
        "dbg = os.environ.get('DEBUG')\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x\n"
    ) == []


def test_ts104_metrics_in_traced_body():
    assert "TS104" in codes(
        "import jax\n"
        "from paddle_tpu.observability import GLOBAL_METRICS\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    GLOBAL_METRICS.counter('c').inc()\n"
        "    return x\n"
    )
    assert "TS104" in codes(
        "import jax\n"
        "from paddle_tpu.observability import get_registry\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    get_registry().counter('c').inc()\n"
        "    return x\n"
    )


def test_ts104_negative_metrics_at_call_site():
    assert codes(
        "import jax\n"
        "from paddle_tpu.observability import GLOBAL_METRICS\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x\n"
        "def serve(x):\n"
        "    y = f(x)\n"
        "    GLOBAL_METRICS.counter('c').inc()\n"
        "    return y\n"
    ) == []


def test_ts105_param_materialization():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)\n"
    )
    assert "TS105" in codes(src)
    assert "TS105" in codes(src.replace("float(x)", "x.item()"))
    # float() of a non-parameter local is not flagged
    assert codes(src.replace("float(x)", "float(1.5) + x")) == []


def test_ts106_global_mutation():
    assert "TS106" in codes(
        "import jax\n"
        "_n = 0\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    global _n\n"
        "    _n += 1\n"
        "    return x\n"
    )
    assert codes(
        "_n = 0\n"
        "def f(x):\n"
        "    global _n\n"
        "    _n += 1\n"
        "    return x\n"
    ) == []


# -- PK: Pallas purity -------------------------------------------------------

def test_pk201_flag_read_in_kernel():
    assert "PK201" in codes(
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "def _add_kernel(x_ref, o_ref):\n"
        "    if GLOBAL_FLAGS.get('benchmark'):\n"
        "        o_ref[...] = x_ref[...]\n"
    )


def test_pk202_metrics_in_kernel():
    assert "PK202" in codes(
        "from paddle_tpu.observability import GLOBAL_METRICS\n"
        "def _add_kernel(x_ref, o_ref):\n"
        "    GLOBAL_METRICS.counter('c').inc()\n"
        "    o_ref[...] = x_ref[...]\n"
    )


def test_pk203_mutable_global_closure():
    src = (
        "_seen = {}\n"
        "NEG_INF = -1e30\n"
        "def _add_kernel(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] + len(_seen) + NEG_INF\n"
    )
    got = codes(src)
    assert "PK203" in got
    # ALL_CAPS literal constants are allowed
    assert got.count("PK203") == 1


def test_pk203_negative_partial_bakes_state():
    assert codes(
        "import functools\n"
        "def _add_kernel(x_ref, o_ref, *, n):\n"
        "    o_ref[...] = x_ref[...] + n\n"
        "kernel = functools.partial(_add_kernel, n=3)\n"
    ) == []


def test_pk204_print_in_kernel_resolved_through_partial():
    # resolution path: pallas_call(k) where k = functools.partial(body, ...)
    assert "PK204" in codes(
        "import functools\n"
        "from jax.experimental import pallas as pl\n"
        "def body(x_ref, o_ref, *, n):\n"
        "    print('tracing')\n"
        "    o_ref[...] = x_ref[...]\n"
        "def run(x):\n"
        "    k = functools.partial(body, n=1)\n"
        "    return pl.pallas_call(k, out_shape=x)(x)\n"
    )


def test_pk204_index_map_lambda():
    assert "PK204" in codes(
        "import time\n"
        "from jax.experimental import pallas as pl\n"
        "spec = pl.BlockSpec((8, 8), lambda i, j: (i, int(time.time())))\n"
    )
    assert codes(
        "from jax.experimental import pallas as pl\n"
        "spec = pl.BlockSpec((8, 8), lambda i, j: (i, j))\n"
    ) == []


# -- FD: flag discipline -----------------------------------------------------

def test_fd301_undefined_flag():
    assert codes(
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "v = GLOBAL_FLAGS.get('definitely_not_a_flag')\n"
    ) == ["FD301"]
    # canonical flags.py names resolve
    assert codes(
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "v = GLOBAL_FLAGS.get('benchmark')\n"
    ) == []


def test_fd301_env_and_setters():
    assert codes("import os\nv = os.environ.get('FLAGS_nope')\n") == ["FD301"]
    assert codes("import os\nv = os.environ['FLAGS_benchmark']\n") == []
    assert codes("from paddle_tpu.flags import set_flags\nset_flags({'FLAGS_typo_flag': 1})\n") == ["FD301"]
    assert codes("from paddle_tpu.flags import get_flags\nget_flags(['benchmark', 'gone_flag'])\n") == ["FD301"]
    # the public attribute-qualified spellings resolve too
    assert codes("import paddle_tpu as paddle\npaddle.set_flags({'FLAGS_typo_flag': 1})\n") == ["FD301"]
    assert codes("import paddle_tpu as paddle\npaddle.set_flags({'FLAGS_benchmark': True})\n") == []


def test_fd301_define_in_same_run_resolves():
    assert codes(
        "from paddle_tpu.flags import GLOBAL_FLAGS, define_flag\n"
        "define_flag('my_new_flag', bool, False)\n"
        "v = GLOBAL_FLAGS.get('my_new_flag')\n"
    ) == []


def test_fd302_loop_read_in_hot_path():
    src = (
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "def scan(items):\n"
        "    for it in items:\n"
        "        if GLOBAL_FLAGS.get('benchmark'):\n"
        "            it.sync()\n"
    )
    assert codes(src, hot_path=True) == ["FD302"]
    assert codes(src, hot_path=False) == []
    hoisted = (
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "def scan(items):\n"
        "    bench = GLOBAL_FLAGS.get('benchmark')\n"
        "    for it in items:\n"
        "        if bench:\n"
        "            it.sync()\n"
    )
    assert codes(hoisted, hot_path=True) == []


# -- EH: exception hygiene ---------------------------------------------------

def test_eh401_bare_except():
    assert codes("try:\n    f()\nexcept:\n    g()\n") == ["EH401"]
    assert codes("try:\n    f()\nexcept ValueError:\n    g()\n") == []


def test_eh402_silent_swallow():
    assert "EH402" in codes("try:\n    f()\nexcept Exception:\n    pass\n")
    # logging the failure is not silent
    assert codes(
        "import logging\n"
        "try:\n"
        "    f()\n"
        "except Exception:  # tolerable: best-effort hook\n"
        "    logging.getLogger(__name__).warning('f failed')\n"
    ) == []


def test_eh403_lint_tags_are_not_reasons():
    # a bare noqa / type: ignore / pragma tag says nothing about WHY breadth
    # is correct — it must not satisfy EH403
    assert codes("try:\n    f()\nexcept Exception:  # noqa: BLE001\n    y = 0\n") == ["EH403"]
    assert codes("try:\n    f()\nexcept Exception:  # type: ignore[misc]\n    y = 0\n") == ["EH403"]
    # a tag FOLLOWED by prose is fine
    assert codes(
        "try:\n    f()\nexcept Exception:  # noqa: BLE001 - fallback covers it\n    y = 0\n"
    ) == []


def test_eh403_broad_except_needs_reason():
    assert codes("try:\n    f()\nexcept Exception as exc:\n    y = 0\n") == ["EH403"]
    assert codes("try:\n    f()\nexcept Exception as exc:  # fallback below\n    y = 0\n") == []
    # comment-only line opening the body also counts (repo idiom)
    assert codes(
        "try:\n"
        "    f()\n"
        "except Exception as exc:\n"
        "    # fallback: the retry path below re-raises on second failure\n"
        "    y = 0\n"
    ) == []


# -- RB: robustness ----------------------------------------------------------

def test_rb501_os_exit_flagged():
    assert codes("import os\ndef f():\n    os._exit(1)\n") == ["RB501"]


def test_rb501_through_import_alias():
    assert codes("import os as _os\ndef f():\n    _os._exit(7)\n") == ["RB501"]
    assert codes("from os import _exit\ndef f():\n    _exit(7)\n") == ["RB501"]
    assert codes("from os import _exit as bail\ndef f():\n    bail(7)\n") == ["RB501"]


def test_rb501_negative_sys_exit_and_other_exits():
    assert codes("import sys\ndef f():\n    sys.exit(1)\n") == []
    assert codes("import os\ndef f():\n    os.kill(1, 9)\n") == []


def test_rb501_allowed_in_watchdog_and_launch():
    src = "import os\ndef f():\n    os._exit(124)\n"
    assert codes(src, path="paddle_tpu/distributed/watchdog.py") == []
    assert codes(src, path="paddle_tpu/distributed/launch/main.py") == []
    assert codes(src, path="paddle_tpu/distributed/launch/sub/mod.py") == []
    # ... but NOT elsewhere under distributed/
    assert codes(src, path="paddle_tpu/distributed/collective.py") == ["RB501"]


def test_rb501_suppressible_with_reason():
    vs = analyze_source(
        "import os\n"
        "def f():\n"
        "    # analysis: disable=RB501 forked child owns no state to flush\n"
        "    os._exit(1)\n"
    )
    assert [v.code for v in vs] == ["RB501"]
    assert vs[0].suppressed and vs[0].reason


# -- RB502: un-timed blocking waits in request-serving paths ------------------

SERVING = "paddle_tpu/serving/worker.py"


def test_rb502_untimed_queue_get_flagged():
    src = "import queue\nq = queue.Queue()\nitem = q.get()\n"
    assert codes(src, path=SERVING) == ["RB502"]
    # from-import constructor form
    src = "from queue import Queue\nq = Queue()\nitem = q.get()\n"
    assert codes(src, path=SERVING) == ["RB502"]


def test_rb502_timed_queue_get_ok():
    assert codes(
        "import queue\nq = queue.Queue()\nitem = q.get(timeout=5)\n", path=SERVING
    ) == []
    # positional form get(block, timeout) and get_nowait are both fine
    assert codes(
        "import queue\nq = queue.Queue()\nitem = q.get(True, 5)\n", path=SERVING
    ) == []
    assert codes(
        "import queue\nq = queue.Queue()\nitem = q.get_nowait()\n", path=SERVING
    ) == []


def test_rb502_dict_get_and_str_join_not_confused_for_waits():
    # constructor tracking: untracked receivers never match
    assert codes("d = {}\nv = d.get('k')\n", path=SERVING) == []
    assert codes("s = ','.join(['a'])\n", path=SERVING) == []
    assert codes("import os\np = os.path.join('a', 'b')\n", path=SERVING) == []


def test_rb502_annotated_assignment_receivers_are_tracked():
    # `self._q: Queue = Queue()` is an AnnAssign — the exact construction
    # style the serving frontend uses; it must not be invisible
    src = (
        "from queue import Queue\n"
        "class H:\n"
        "    def __init__(self):\n"
        "        self._q: Queue = Queue()\n"
        "    def take(self):\n"
        "        return self._q.get()\n"
    )
    assert codes(src, path=SERVING) == ["RB502"]
    assert codes(src.replace(".get()", ".get(timeout=1)"), path=SERVING) == []


def test_rb502_event_wait_and_thread_join():
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._done = threading.Event()\n"
        "        self._t = threading.Thread(target=print)\n"
        "    def finish(self):\n"
        "        self._done.wait()\n"
        "        self._t.join()\n"
    )
    assert codes(src, path="paddle_tpu/inference/x.py") == ["RB502", "RB502"]
    timed = src.replace(".wait()", ".wait(timeout=2)").replace(".join()", ".join(5)")
    assert codes(timed, path="paddle_tpu/inference/x.py") == []


def test_rb502_socket_recv_needs_settimeout():
    src = "import socket\ns = socket.socket()\ndata = s.recv(1024)\n"
    assert codes(src, path="paddle_tpu/distributed/x.py") == ["RB502"]
    timed = "import socket\ns = socket.socket()\ns.settimeout(3)\ndata = s.recv(1024)\n"
    assert codes(timed, path="paddle_tpu/distributed/x.py") == []


def test_rb502_only_in_request_serving_dirs():
    src = "import queue\nq = queue.Queue()\nitem = q.get()\n"
    assert codes(src, path="paddle_tpu/models/x.py") == []
    assert codes(src, path="paddle_tpu/kernels/x.py") == []
    for gated in ("serving", "distributed", "inference"):
        assert codes(src, path=f"paddle_tpu/{gated}/x.py") == ["RB502"]


def test_rb502_suppressible_with_reason():
    vs = analyze_source(
        "import queue\n"
        "q = queue.Queue()\n"
        "# analysis: disable=RB502 shutdown path; producer provably alive\n"
        "item = q.get()\n",
        path=SERVING,
    )
    assert [v.code for v in vs] == ["RB502"]
    assert vs[0].suppressed and vs[0].reason


# -- RB503: unbounded retry loops in request-serving paths --------------------

def test_rb503_unbounded_retry_loop_flagged():
    # success-exit alone is NOT a bound: a permanently-dead dependency
    # never delivers success
    src = (
        "def pump(router):\n"
        "    while True:\n"
        "        ok = router.redispatch()\n"
        "        if ok:\n"
        "            break\n"
    )
    assert codes(src, path=SERVING) == ["RB503"]
    # recover()-shaped retries too
    src = "def f(engine):\n    while True:\n        engine.recover()\n"
    assert codes(src, path=SERVING) == ["RB503"]


def test_rb503_attempt_counter_bounds_the_loop():
    src = (
        "def f(x, max_attempts):\n"
        "    attempt = 0\n"
        "    while True:\n"
        "        attempt += 1\n"
        "        if attempt >= max_attempts:\n"
        "            raise RuntimeError('retries exhausted')\n"
        "        if retry_step(x):\n"
        "            return\n"
    )
    assert codes(src, path=SERVING) == []


def test_rb503_deadline_and_expired_checks_bound_the_loop():
    src = (
        "import time\n"
        "def f(req, deadline):\n"
        "    while True:\n"
        "        if time.perf_counter() >= deadline:\n"
        "            raise TimeoutError()\n"
        "        recover(req)\n"
    )
    assert codes(src, path=SERVING) == []
    src = (
        "def f(req):\n"
        "    while True:\n"
        "        if req.expired():\n"
        "            raise TimeoutError()\n"
        "        redispatch(req)\n"
    )
    assert codes(src, path=SERVING) == []


def test_rb503_conditioned_while_and_non_retry_loops_ok():
    # a conditioned while IS its own bound
    src = (
        "def f(r, n):\n"
        "    i = 0\n"
        "    while i < n:\n"
        "        r.redispatch()\n"
        "        i += 1\n"
    )
    assert codes(src, path=SERVING) == []
    # while True without a retry-shaped call is not this checker's business
    src = (
        "def f(q):\n"
        "    while True:\n"
        "        item = q.get_nowait()\n"
        "        if item is None:\n"
        "            break\n"
    )
    assert codes(src, path=SERVING) == []


def test_rb503_only_in_request_serving_dirs():
    src = "def f(r):\n    while True:\n        r.redispatch()\n"
    assert codes(src, path="paddle_tpu/models/x.py") == []
    for gated in ("serving", "distributed", "inference"):
        assert codes(src, path=f"paddle_tpu/{gated}/x.py") == ["RB503"]


def test_rb503_nested_function_retry_is_not_the_outer_loops_problem():
    # a closure's retry belongs to that function's own loop discipline
    src = (
        "def f(q):\n"
        "    while True:\n"
        "        def later():\n"
        "            retry_op()\n"
        "        item = q.get_nowait()\n"
        "        if item is None:\n"
        "            break\n"
    )
    assert codes(src, path=SERVING) == []


def test_rb503_suppressible_with_reason():
    vs = analyze_source(
        "def f(r):\n"
        "    # analysis: disable=RB503 bounded by the caller's watchdog\n"
        "    while True:\n"
        "        r.redispatch()\n",
        path=SERVING,
    )
    assert [v.code for v in vs] == ["RB503"]
    assert vs[0].suppressed and vs[0].reason


# -- OB: observability discipline --------------------------------------------

def test_ob601_span_opened_without_with_leaks():
    # armed Span assigned to a variable: __exit__ never runs, silent leak
    assert codes('sp = tracer.span("phase")\n') == ["OB601"]
    assert codes('x = self._tracer.span("phase")\n') == ["OB601"]
    assert codes('GLOBAL_TRACER.span("phase")\n') == ["OB601"]
    assert codes('s = get_tracer().span("phase")\n') == ["OB601"]


def test_ob601_with_statement_and_retroactive_forms_ok():
    assert codes('with tracer.span("phase") as sp:\n    sp.set_attr("k", 1)\n') == []
    # add_span/add_event take explicit timestamps: no with required
    assert codes('tracer.add_span("phase", start_s=0.0, end_s=1.0)\n') == []
    assert codes('tracer.add_event("mark")\n') == []


def test_ob601_unrelated_span_and_record_receivers_not_confused():
    # .span on a non-tracer receiver, .record on a non-recorder receiver
    assert codes('cell.span(3)\n') == []
    assert codes('db.record("row")\n') == []
    assert codes('wingspan = bird.span("wide")\n') == []


def test_ob601_emission_inside_jitted_body():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    with tracer.span('inner'):\n"
        "        return x\n"
    )
    assert codes(src) == ["OB601"]
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    record_event('admit', req_id=1)\n"
        "    return x\n"
    )
    assert codes(src) == ["OB601"]
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    GLOBAL_FLIGHT_RECORDER.record('admit', req_id=1)\n"
        "    return x\n"
    )
    assert codes(src) == ["OB601"]


def test_ob601_emission_inside_pallas_kernel():
    src = (
        "import jax.experimental.pallas as pl\n"
        "def my_kernel(x_ref, o_ref):\n"
        "    record_event('tile')\n"
        "    o_ref[...] = x_ref[...]\n"
        "def run(x):\n"
        "    return pl.pallas_call(my_kernel, out_shape=x)(x)\n"
    )
    assert codes(src) == ["OB601"]


def test_ob601_host_call_site_pattern_is_clean():
    # the sanctioned shape: dispatch inside jit, emission at the call site
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x * 2\n"
        "def drive(x):\n"
        "    y = step(x)\n"
        "    record_event('stepped')\n"
        "    with tracer.span('post') as sp:\n"
        "        sp.set_attr('ok', True)\n"
        "    return y\n"
    )
    assert codes(src) == []


def test_ob601_suppressible_with_reason():
    vs = analyze_source(
        "# analysis: disable=OB601 span handed to a helper that closes it\n"
        "sp = tracer.span('phase')\n"
    )
    assert [v.code for v in vs] == ["OB601"]
    assert vs[0].suppressed and vs[0].reason


def test_ob602_typo_in_registry_read_fires():
    # .family() is the strict-read API: any receiver counts
    assert codes('fam = registry.family("bogus_family_name_total")\n') == ["OB602"]
    # .get() on a registry-shaped receiver
    assert codes('fam = GLOBAL_METRICS.get("bogus_family_name_total")\n') == ["OB602"]
    assert codes('fam = self._registry.get("bogus_family_name_total")\n') == ["OB602"]
    assert codes('fam = get_registry().get("bogus_family_name_total")\n') == ["OB602"]


def test_ob602_registered_names_resolve():
    # a name defined in the SAME snippet resolves
    src = (
        'c = reg.counter("snippet_family_total", "help")\n'
        'back = GLOBAL_METRICS.get("snippet_family_total")\n'
    )
    assert codes(src) == []
    # a real package family resolves through the canonical package scan
    assert codes(
        'fam = registry.family("engine_requests_admitted_total")\n'
    ) == []
    assert codes('fam = registry.family("serving_shed_total")\n') == []


def test_ob602_non_registry_receivers_not_confused():
    # dict/config .get with a literal is NOT a registry read
    assert codes('v = cfg.get("whatever_key")\n') == []
    assert codes('v = self._metrics.get("shed")\n') == []
    assert codes('v = os.environ.get("PATH")\n') == []
    # dynamic names are out of static scope (runtime family() raises)
    assert codes("fam = registry.family(name)\n") == []


def test_ob602_suppressible_with_reason():
    vs = analyze_source(
        "# analysis: disable=OB602 family registered by an optional plugin\n"
        'fam = registry.family("plugin_only_family_total")\n'
    )
    assert [v.code for v in vs] == ["OB602"]
    assert vs[0].suppressed and vs[0].reason


def test_ob602_fleet_family_list_resolves():
    # the aggregation module's whole literal list must resolve: the drift
    # this checker exists for is exactly a rename desynchronizing these
    from paddle_tpu.analysis.checkers.observability import (
        _package_family_universe,
    )
    from paddle_tpu.observability.aggregate import FLEET_COUNTER_FAMILIES

    universe = _package_family_universe()
    missing = [n for n in FLEET_COUNTER_FAMILIES if n not in universe]
    assert not missing, f"fleet families not registered anywhere: {missing}"


def test_ob603_timed_dispatch_without_sync_fires():
    # perf_counter pair brackets a jitted call with no device sync before
    # the stop timestamp: the "measured" time is dispatch, not execution
    assert codes(
        "import jax, time\n"
        "def g(x):\n"
        "    return x\n"
        "f = jax.jit(g)\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f(x)\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0, y\n"
    ) == ["OB603"]


def test_ob603_self_attribute_jitted_callable():
    assert codes(
        "import jax, time\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._fn = jax.jit(lambda x: x)\n"
        "    def step(self, x):\n"
        "        t0 = time.time()\n"
        "        y = self._fn(x)\n"
        "        t1 = time.time()\n"
        "        return t1 - t0, y\n"
    ) == ["OB603"]


def test_ob603_sync_before_stop_is_honest():
    assert codes(
        "import jax, time\n"
        "def g(x):\n"
        "    return x\n"
        "f = jax.jit(g)\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f(x)\n"
        "    jax.block_until_ready(y)\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0, y\n"
    ) == []


def test_ob603_fused_dispatch_and_sync_in_one_statement():
    # np.asarray(f(x)) blocks on the result in the same statement: honest
    assert codes(
        "import jax, time\n"
        "import numpy as np\n"
        "def g(x):\n"
        "    return x\n"
        "f = jax.jit(g)\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = np.asarray(f(x))\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0, y\n"
    ) == []


def test_ob603_non_jitted_call_not_confused():
    assert codes(
        "import time\n"
        "def helper(x):\n"
        "    return x + 1\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = helper(x)\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0, y\n"
    ) == []


def test_ob603_dispatch_before_first_timestamp_not_flagged():
    # a jitted warmup call ahead of the timing window is fine
    assert codes(
        "import jax, time\n"
        "def g(x):\n"
        "    return x\n"
        "f = jax.jit(g)\n"
        "def bench(x):\n"
        "    y = f(x)\n"
        "    jax.block_until_ready(y)\n"
        "    t0 = time.perf_counter()\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0\n"
    ) == []


def test_ob603_suppressible_with_reason():
    vs = analyze_source(
        "import jax, time\n"
        "def g(x):\n"
        "    return x\n"
        "f = jax.jit(g)\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f(x)\n"
        "    # analysis: disable=OB603 dispatch cost is the quantity under test\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0, y\n"
    )
    ob = [v for v in vs if v.code == "OB603"]
    assert len(ob) == 1
    assert ob[0].suppressed and ob[0].reason


# -- suppressions ------------------------------------------------------------

def test_suppression_with_reason():
    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "except:  # analysis: disable=EH401 exercised by fixture\n"
        "    g()\n"
    )
    assert len(vs) == 1 and vs[0].suppressed and vs[0].reason == "exercised by fixture"


def test_suppression_on_preceding_comment_line():
    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "# analysis: disable=EH401 fixture wants it suppressed\n"
        "except:\n"
        "    g()\n"
    )
    assert [v.suppressed for v in vs] == [True]


def test_suppression_without_reason_does_not_suppress():
    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "except:  # analysis: disable=EH401\n"
        "    g()\n"
    )
    assert len(vs) == 1 and not vs[0].suppressed
    assert "missing reason" in vs[0].message


def test_suppression_wrong_code_does_not_suppress():
    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "except:  # analysis: disable=TS101 not the right code\n"
        "    g()\n"
    )
    assert len(vs) == 1 and not vs[0].suppressed


def test_suppression_preceding_line_wins_over_unrelated_inline_disable():
    # an inline disable for a DIFFERENT code must not mask a valid
    # suppression sitting on the preceding comment line
    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "# analysis: disable=EH401 fixture suppresses the bare except\n"
        "except:  # analysis: disable=TS101 unrelated code\n"
        "    g()\n"
    )
    assert [v.suppressed for v in vs] == [True]
    assert vs[0].reason == "fixture suppresses the bare except"


def test_suppression_multiple_codes():
    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "except:  # analysis: disable=TS101,EH401 fixture covers both\n"
        "    g()\n"
    )
    assert [v.suppressed for v in vs] == [True]


# -- reporters + registry ----------------------------------------------------

def test_reporters_and_summary():
    vs = analyze_source("try:\n    f()\nexcept:\n    pass\n")
    data = json.loads(render_json(vs))
    assert data["summary"]["unsuppressed"] == len(vs) >= 1
    assert {v["code"] for v in data["violations"]} >= {"EH401"}
    text = render_text(vs)
    assert "EH401" in text and "unsuppressed" in text


def test_checker_codes_unique_and_documented():
    table = all_codes()
    assert {"TS101", "PK201", "FD301", "EH401"} <= set(table)
    for checker in all_checkers():
        for code, desc in checker.codes.items():
            assert desc, code


# -- CLI ---------------------------------------------------------------------

def _run_cli(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    f()\nexcept:\n    pass\n")
    good = tmp_path / "good.py"
    good.write_text("def f():\n    return 1\n")
    r = _run_cli([str(bad)])
    assert r.returncode == 1 and "EH401" in r.stdout
    r = _run_cli(["--format", "json", str(good)])
    assert r.returncode == 0
    assert json.loads(r.stdout)["summary"]["unsuppressed"] == 0


def test_cli_missing_path_is_a_usage_error(tmp_path):
    # a typo'd target must not become a vacuous zero-file clean pass
    r = _run_cli([str(tmp_path / "no_such_dir")])
    assert r.returncode == 2 and "no such file" in r.stderr
    # ... and neither must an existing directory holding no Python files
    empty = tmp_path / "empty"
    empty.mkdir()
    r = _run_cli([str(empty)])
    assert r.returncode == 2 and "no Python files" in r.stderr


def test_cli_select_unknown_code_is_a_usage_error(tmp_path):
    # the same never-vacuous rule: a typo'd --select used to filter every
    # finding and exit 0, so a CI invocation passed without checking anything
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    f()\nexcept:\n    pass\n")
    r = _run_cli(["--select", "EH999", str(bad)])
    assert r.returncode == 2
    assert "EH999" in r.stderr and "valid codes" in r.stderr
    assert "EH401" in r.stderr  # the list names what IS registered
    # a valid prefix mixed with a bogus one still errors (no partial pass)
    r = _run_cli(["--select", "EH,TYPO", str(bad)])
    assert r.returncode == 2 and "TYPO" in r.stderr
    # family prefixes and exact codes stay accepted
    r = _run_cli(["--select", "EH", str(bad)])
    assert r.returncode == 1 and "EH401" in r.stdout
    r = _run_cli(["--select", "EH401", str(bad)])
    assert r.returncode == 1


def _run_cli_in(cwd, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_cli_changed_only_scopes_to_git_diff(tmp_path):
    def git(*argv):
        subprocess.run(
            ["git", *argv], cwd=tmp_path, check=True, capture_output=True,
            env=dict(
                os.environ,
                GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t",
            ),
        )

    git("init", "-q")
    clean = tmp_path / "clean.py"
    clean.write_text("try:\n    f()\nexcept:\n    pass\n")  # committed finding
    git("add", "clean.py")
    git("commit", "-qm", "base")
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    g()\nexcept:\n    pass\n")  # untracked finding
    # only the changed file is analyzed: clean.py's finding does not gate
    r = _run_cli_in(tmp_path, ["--changed-only=HEAD", "."])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "bad.py" in r.stdout and "clean.py" not in r.stdout
    # everything committed: nothing changed -> clean exit, nothing analyzed
    git("add", "bad.py")
    git("commit", "-qm", "rest")
    r = _run_cli_in(tmp_path, ["--changed-only=HEAD", "."])
    assert r.returncode == 0 and "no Python files changed" in r.stdout


def test_cli_changed_only_falls_back_without_git(tmp_path):
    # outside any repo (or with a bad ref) the mode must degrade to a FULL
    # run with a warning — never a vacuous zero-file pass
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    f()\nexcept:\n    pass\n")
    r = _run_cli_in(tmp_path, ["--changed-only=not-a-real-ref", "."])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "falling back to a full run" in r.stderr
    assert "bad.py" in r.stdout


def test_autotune_verbose_handler_follows_the_flag():
    import logging

    import paddle_tpu as paddle
    from paddle_tpu.kernels.autotune import _logger, _verbose_state

    prior = _logger.level
    try:
        paddle.set_flags({"FLAGS_kernel_autotune_verbose": True})
        assert _verbose_state and _verbose_state[0] in _logger.handlers
        paddle.set_flags({"FLAGS_kernel_autotune_verbose": False})
        assert not _verbose_state
        assert not any(isinstance(h, logging.StreamHandler) for h in _logger.handlers)
        assert _logger.level == prior
    finally:
        paddle.set_flags({"FLAGS_kernel_autotune_verbose": False})
        _logger.setLevel(prior)


# -- dataflow layer: thread-entry discovery ----------------------------------

def _graph_of(src, path="<snippet>.py"):
    import ast as _ast

    from paddle_tpu.analysis.dataflow import PackageIndex

    idx = PackageIndex()
    return idx, idx.add_module(path, _ast.parse(src))


def test_thread_entry_thread_target_self_method():
    _, g = _graph_of(
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run, daemon=True)\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    assert ("S._run", "thread") in {(q, k) for q, k, _ in g.thread_entries}


def test_thread_entry_module_function_target():
    _, g = _graph_of(
        "import threading\n"
        "def worker():\n"
        "    pass\n"
        "t = threading.Thread(target=worker)\n"
    )
    assert ("worker", "thread") in {(q, k) for q, k, _ in g.thread_entries}


def test_thread_entry_http_handler_methods():
    _, g = _graph_of(
        "from http.server import BaseHTTPRequestHandler\n"
        "class H(BaseHTTPRequestHandler):\n"
        "    def do_GET(self):\n"
        "        pass\n"
        "    def _helper(self):\n"
        "        pass\n"
    )
    kinds = {(q, k) for q, k, _ in g.thread_entries}
    assert ("H.do_GET", "handler") in kinds and ("H._helper", "handler") in kinds


def test_thread_entry_flag_listener():
    _, g = _graph_of(
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "def _refresh(value):\n"
        "    pass\n"
        "GLOBAL_FLAGS.on_change('enable_metrics', _refresh)\n"
    )
    assert ("_refresh", "listener") in {(q, k) for q, k, _ in g.thread_entries}


def test_jit_wrapper_conditional_donate_argnums_resolves():
    """The engine's `(1,) if donate else ()` idiom yields position 1."""
    _, g = _graph_of(
        "import jax\n"
        "class E:\n"
        "    def __init__(self, impl, donate):\n"
        "        self._fn = jax.jit(impl, donate_argnums=(1,) if donate else ())\n"
    )
    w = g.jit_wrappers[("E", "self._fn")]
    assert w.donated == frozenset({1})


def test_package_index_memoizes_per_module_graphs():
    import ast as _ast

    from paddle_tpu.analysis.dataflow import PackageIndex

    idx = PackageIndex()
    tree = _ast.parse("def f():\n    pass\n")
    idx.add_module("a.py", tree)
    idx.add_module("a.py", tree)
    idx.add_module("a.py", tree)
    assert idx.build_count == 1


# -- CC: concurrency ---------------------------------------------------------

_CC_THREADED_CLASS = (
    "import threading\n"
    "class Server:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._jobs = {}\n"
    "        self._t = threading.Thread(target=self._run)\n"
    "    def _run(self):\n"
    "        while True:\n"
    "            with self._lock:\n"
    "                self._jobs['x'] = 1\n"
)


def test_cc701_unguarded_read_of_guarded_field():
    src = _CC_THREADED_CLASS + (
        "    def peek(self):\n"
        "        return self._jobs.get('x')\n"
    )
    assert "CC701" in codes(src)


def test_cc701_negative_all_accesses_locked():
    src = _CC_THREADED_CLASS + (
        "    def peek(self):\n"
        "        with self._lock:\n"
        "            return self._jobs.get('x')\n"
    )
    assert codes(src) == []


def test_cc701_negative_helper_inherits_lock_from_call_sites():
    """A helper whose every call site holds the lock is effectively locked
    (interprocedural fixpoint) — the frontend's submit->_tenant_label shape."""
    src = _CC_THREADED_CLASS + (
        "    def _peek_locked(self):\n"
        "        return self._jobs.get('x')\n"
        "    def peek(self):\n"
        "        with self._lock:\n"
        "            return self._peek_locked()\n"
    )
    assert codes(src) == []


def test_cc701_negative_no_thread_seam_means_silence():
    """A lock-owning class with no thread entry anywhere never fires —
    single-threaded code with a vestigial lock is not a race."""
    src = (
        "import threading\n"
        "class Quiet:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._jobs = {}\n"
        "    def put(self):\n"
        "        with self._lock:\n"
        "            self._jobs['x'] = 1\n"
        "    def peek(self):\n"
        "        return self._jobs.get('x')\n"
    )
    assert codes(src) == []


def test_cc701_negative_sync_primitive_fields_exempt():
    src = _CC_THREADED_CLASS + (
        "    def wait(self):\n"
        "        self._evt = threading.Event()\n"
        "        self._evt.wait(1.0)\n"
    )
    assert codes(src) == []


def test_cc702_inverted_lock_order():
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n"
        "        self._lb = threading.Lock()\n"
        "        threading.Thread(target=self.f1).start()\n"
        "    def f1(self):\n"
        "        with self._la:\n"
        "            with self._lb:\n"
        "                pass\n"
        "    def f2(self):\n"
        "        with self._lb:\n"
        "            with self._la:\n"
        "                pass\n"
    )
    assert "CC702" in codes(src)


def test_cc702_negative_consistent_order():
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n"
        "        self._lb = threading.Lock()\n"
        "        threading.Thread(target=self.f1).start()\n"
        "    def f1(self):\n"
        "        with self._la:\n"
        "            with self._lb:\n"
        "                pass\n"
        "    def f2(self):\n"
        "        with self._la:\n"
        "            with self._lb:\n"
        "                pass\n"
    )
    assert codes(src) == []


def test_cc702_interprocedural_through_call_edge():
    """f2 holds lb and calls g which takes la — inverted vs f1's la->lb."""
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n"
        "        self._lb = threading.Lock()\n"
        "        threading.Thread(target=self.f1).start()\n"
        "    def f1(self):\n"
        "        with self._la:\n"
        "            with self._lb:\n"
        "                pass\n"
        "    def g(self):\n"
        "        with self._la:\n"
        "            pass\n"
        "    def f2(self):\n"
        "        with self._lb:\n"
        "            self.g()\n"
    )
    assert "CC702" in codes(src)


def test_cc703_iteration_outside_lock():
    src = _CC_THREADED_CLASS + (
        "    def snapshot(self):\n"
        "        return list(self._jobs)\n"
    )
    assert "CC703" in codes(src)


def test_cc703_negative_iteration_under_lock():
    src = _CC_THREADED_CLASS + (
        "    def snapshot(self):\n"
        "        with self._lock:\n"
        "            return list(self._jobs)\n"
    )
    assert codes(src) == []


_CC704_HOT_LOOP = (
    "from paddle_tpu.flags import GLOBAL_FLAGS\n"
    "def dispatch(x):\n"
    "    if GLOBAL_FLAGS.get('check_nan_inf'):\n"
    "        scan(x)\n"
    "    return x\n"
    "def run(xs):\n"
    "    out = []\n"
    "    for x in xs:\n"
    "        out.append(dispatch(x))\n"
    "    return out\n"
)


def test_cc704_reverted_nan_check_shape_is_flagged():
    """Regression fixture: the pre-PR3 core/dispatch.py shape — a registry
    read inside a function the call graph reaches from a loop. FD302 could
    not see this (no syntactic loop around the read); the interprocedural
    pass can."""
    assert "CC704" in codes(_CC704_HOT_LOOP, hot_path=True)


def test_cc704_negative_outside_hot_path_modules():
    assert codes(_CC704_HOT_LOOP, hot_path=False) == []


def test_cc704_negative_unreachable_from_any_loop():
    src = (
        "from paddle_tpu.flags import GLOBAL_FLAGS\n"
        "def configure():\n"
        "    return GLOBAL_FLAGS.get('check_nan_inf')\n"
    )
    assert codes(src, hot_path=True) == []


def test_cc704_current_dispatch_module_is_clean():
    """The fixed core/dispatch.py (_NAN_CHECK cached locals) stays clean."""
    vs = analyze_paths([str(PKG / "core" / "dispatch.py")], select=["CC704"])
    assert [v for v in vs if not v.suppressed] == []


# -- DN: donation / buffer lifetime ------------------------------------------

_DN_ENGINE_HEADER = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "import numpy as np\n"
    "class Eng:\n"
    "    def __init__(self, impl):\n"
    "        self._fn = jax.jit(impl, donate_argnums=(1,))\n"
    "        self._state = init()\n"
    "        self._ntok = np.zeros((4,), np.int32)\n"
    "        self._last_tok = np.zeros((4,), np.int32)\n"
)


def test_dn801_read_after_donate():
    src = _DN_ENGINE_HEADER + (
        "    def step(self, x):\n"
        "        out, new_state = self._fn(x, self._state)\n"
        "        y = self._state.sum()\n"
        "        self._state = new_state\n"
        "        return out, y\n"
    )
    assert "DN801" in codes(src)


def test_dn801_negative_donate_and_rebind_same_statement():
    src = _DN_ENGINE_HEADER + (
        "    def step(self, x):\n"
        "        out, self._state = self._fn(x, self._state)\n"
        "        return out\n"
    )
    assert codes(src) == []


def test_dn801_mutation_after_donate():
    src = _DN_ENGINE_HEADER + (
        "    def step(self, x):\n"
        "        out, new_state = self._fn(x, self._state)\n"
        "        self._state[0] = 0\n"
        "        return out\n"
    )
    assert "DN801" in codes(src)


def test_dn801_negative_read_in_untaken_branch_arm():
    """A donate in the `if` arm must not taint the sibling `else` arm."""
    src = _DN_ENGINE_HEADER + (
        "    def step(self, x, fast):\n"
        "        if fast:\n"
        "            out, self._state = self._fn(x, self._state)\n"
        "        else:\n"
        "            out = slow(x, self._state)\n"
        "        return out\n"
    )
    assert codes(src) == []


def test_dn802_replay_race_minimized_pr6_replica():
    """The PR 6 recovery-replay race, minimized: host vectors handed to the
    decode dispatch WITHOUT .copy(), then mutated in the same loop body —
    replay never syncs (the emitted tokens are discarded), so the async
    dispatch still aliases the numpy memory being mutated."""
    src = _DN_ENGINE_HEADER + (
        "    def replay(self, tables, depth):\n"
        "        for r in range(depth):\n"
        "            lens = jnp.asarray(self._ntok)\n"
        "            toks = jnp.asarray(self._last_tok)\n"
        "            _nxt, self._state = self._fn(toks, self._state, lens)\n"
        "            for i in range(4):\n"
        "                self._ntok[i] += 1\n"
        "                self._last_tok[i] = 7\n"
    )
    found = codes(src)
    assert "DN802" in found, found


def test_dn802_negative_snapshot_copy_is_the_fix():
    """jnp.asarray(buf.copy()) — the exact PR 6 fix shape — is clean."""
    src = _DN_ENGINE_HEADER + (
        "    def replay(self, tables, depth):\n"
        "        for r in range(depth):\n"
        "            lens = jnp.asarray(self._ntok.copy())\n"
        "            toks = jnp.asarray(self._last_tok.copy())\n"
        "            _nxt, self._state = self._fn(toks, self._state, lens)\n"
        "            for i in range(4):\n"
        "                self._ntok[i] += 1\n"
        "                self._last_tok[i] = 7\n"
    )
    assert codes(src) == []


def test_dn802_chunked_dispatch_block_table_mutation():
    """Chunked-prefill shape of the replay race: the per-slot block table
    (host numpy) is handed to the unified mixed prefill/decode dispatch,
    then mutated (a new block appended for the next chunk) before any sync
    point — the async dispatch still aliases the table memory."""
    src = _DN_ENGINE_HEADER + (
        "    def chunk_steps(self, depth):\n"
        "        for r in range(depth):\n"
        "            tables = jnp.asarray(self._ntok)\n"
        "            q_lens = jnp.asarray(self._last_tok)\n"
        "            _nxt, self._state = self._fn(tables, self._state, q_lens)\n"
        "            for i in range(4):\n"
        "                self._ntok[i] = 9\n"
        "                self._last_tok[i] += 1\n"
    )
    found = codes(src)
    assert "DN802" in found, found


def test_dn802_negative_chunked_dispatch_synced_then_mutated():
    """The engine's actual unified-step shape: np.asarray(nxt) syncs the
    dispatch before _ntok advances and the tables regrow — clean."""
    src = _DN_ENGINE_HEADER + (
        "    def chunk_steps(self, depth):\n"
        "        for r in range(depth):\n"
        "            tables = jnp.asarray(self._ntok)\n"
        "            q_lens = jnp.asarray(self._last_tok)\n"
        "            nxt, self._state = self._fn(tables, self._state, q_lens)\n"
        "            nxt = np.asarray(nxt)\n"
        "            for i in range(4):\n"
        "                self._ntok[i] = 9\n"
        "                self._last_tok[i] += 1\n"
    )
    assert codes(src) == []


def test_dn802_negative_sync_point_before_mutation():
    """The normal step path: np.asarray(result) syncs before the host-side
    vectors are mutated — exactly why step() is safe without copies."""
    src = _DN_ENGINE_HEADER + (
        "    def step(self):\n"
        "        lens = jnp.asarray(self._ntok)\n"
        "        nxt, self._state = self._fn(jnp.asarray(self._last_tok), self._state, lens)\n"
        "        nxt = np.asarray(nxt)\n"
        "        self._ntok[0] += 1\n"
        "        self._last_tok[0] = int(nxt[0])\n"
    )
    assert codes(src) == []


def test_dn803_record_between_dispatch_and_commit():
    src = (
        "import jax\n"
        "from paddle_tpu.observability.recompile import GLOBAL_WATCHDOG\n"
        "class SF:\n"
        "    def __init__(self, impl):\n"
        "        self._fn = jax.jit(impl, donate_argnums=(1,))\n"
        "        self._state = init()\n"
        "    def __call__(self, x):\n"
        "        out, new_state = self._fn(x, self._state)\n"
        "        GLOBAL_WATCHDOG.record_compile('sf', signature='x')\n"
        "        self._state = new_state\n"
        "        return out\n"
    )
    assert "DN803" in codes(src)


def test_dn_local_wrapper_name_does_not_leak_across_functions():
    """A bare-name jit wrapper bound INSIDE one function must not make a
    same-named local in another function look like a donating dispatch
    (review repro: `step` in build() vs a plain callable `step` elsewhere)."""
    src = (
        "import jax\n"
        "def build(impl):\n"
        "    step = jax.jit(impl, donate_argnums=(1,))\n"
        "    return step\n"
        "def other(x, state, make_plain):\n"
        "    step = make_plain()\n"
        "    out = step(x, state)\n"
        "    y = state.sum()\n"
        "    return out, y\n"
    )
    assert codes(src) == []


def test_dn_module_level_wrapper_applies_module_wide():
    src = (
        "import jax\n"
        "_step = jax.jit(impl, donate_argnums=(1,))\n"
        "def use(x, state):\n"
        "    out, new_state = _step(x, state)\n"
        "    y = state.sum()\n"
        "    return out, y\n"
    )
    assert "DN801" in codes(src)


def test_dn_rebound_wrapper_name_stops_donating():
    """Rebinding the wrapper name to a plain callable kills its donation
    semantics for the rest of the function."""
    src = (
        "import jax\n"
        "def use(x, state, plain):\n"
        "    step = jax.jit(impl, donate_argnums=(1,))\n"
        "    step = plain\n"
        "    out = step(x, state)\n"
        "    y = state.sum()\n"
        "    return out, y\n"
    )
    assert codes(src) == []


def test_dn803_negative_record_after_commit():
    src = (
        "import jax\n"
        "from paddle_tpu.observability.recompile import GLOBAL_WATCHDOG\n"
        "class SF:\n"
        "    def __init__(self, impl):\n"
        "        self._fn = jax.jit(impl, donate_argnums=(1,))\n"
        "        self._state = init()\n"
        "    def __call__(self, x):\n"
        "        out, new_state = self._fn(x, self._state)\n"
        "        self._state = new_state\n"
        "        GLOBAL_WATCHDOG.record_compile('sf', signature='x')\n"
        "        return out\n"
    )
    assert codes(src) == []


def test_dn_engine_module_is_clean():
    """inference/engine.py (donate-and-rebind + snapshot-copy replay + sync
    before mutation) passes the DN family as written."""
    vs = analyze_paths([str(PKG / "inference" / "engine.py")], select=["DN"])
    assert [v for v in vs if not v.suppressed] == []


# -- TB: tape backward discipline ---------------------------------------------

def test_tb901_grad_over_kernel_function():
    src = """
import jax
from jax.experimental import pallas as pl

def my_op(x):
    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)

g = jax.grad(my_op)(1.0)
"""
    assert codes(src) == ["TB901"]


def test_tb901_vjp_over_one_hop_wrapper_and_lambda():
    src = """
import jax
from jax.experimental import pallas as pl

def my_op(x):
    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)

def wrapper(x):
    return my_op(x) * 2.0

h = jax.vjp(wrapper, 1.0)
i = jax.value_and_grad(lambda x: my_op(x))(1.0)
"""
    assert codes(src) == ["TB901", "TB901"]


def test_tb901_from_jax_import_alias():
    src = """
from jax import grad
from jax.experimental import pallas as pl

def my_op(x):
    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)

g = grad(my_op)(1.0)
"""
    assert codes(src) == ["TB901"]


def test_tb901_negative_custom_vjp_forms():
    """Decorator, assignment, and factory-shell wiring all define their own
    AD rule — none may fire."""
    src = """
import jax
from jax.experimental import pallas as pl

@jax.custom_vjp
def decorated(x):
    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)

def assigned_raw(x):
    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)

core = jax.custom_vjp(assigned_raw)

def shell(engine_fwd):
    @jax.custom_vjp
    def inner(x):
        return engine_fwd(x)
    return inner

def factory(x):
    def engine_fwd(x):
        return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
    return shell(engine_fwd)

j = jax.grad(decorated)(1.0)
k = jax.grad(core)(1.0)
m = jax.vjp(factory, 1.0)
"""
    assert codes(src) == []


def test_tb901_negative_generic_dispatch_parameter():
    """The tape's own ``jax.vjp(fn, ...)`` over a caller-supplied function is
    unresolvable by design and stays clean."""
    src = """
import jax

def generic(fn, *arrays):
    out, vjp_fn = jax.vjp(fn, *arrays)
    return out, vjp_fn
"""
    assert codes(src) == []


def test_tb901_kernel_package_self_run_clean():
    """The fused-op modules differentiate through tape GradNodes or
    custom_vjp only — the kernels package passes TB as written."""
    vs = analyze_paths([str(PKG / "kernels")], select=["TB"])
    assert [v for v in vs if not v.suppressed] == []


# -- PG: Pallas kernel geometry ----------------------------------------------

_PG_PRELUDE = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from jax.experimental import pallas as pl\n"
    "def k(x_ref, o_ref):\n"
    "    o_ref[...] = x_ref[...]\n"
)


def _pg_site(shape_in, shape_out, grid="(4,)", map_in="lambda i: (i, 0)"):
    return (
        _PG_PRELUDE
        + "def f():\n"
        "    x = jnp.zeros((256, 8), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        f"        grid={grid},\n"
        f"        in_specs=[pl.BlockSpec({shape_in}, {map_in})],\n"
        f"        out_specs=pl.BlockSpec({shape_out}, lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((256, 8), jnp.float32),\n"
        "    )(x)\n"
    )


def test_pg901_block_rank_vs_map_arity():
    # 3-dim block shape against a 2-tuple index map: Mosaic would reject it
    # at first lowering; here it fails at lint time
    assert "PG901" in codes(_pg_site("(64, 8, 1)", "(64, 8)"))


def test_pg901_negative_consistent_geometry():
    assert codes(_pg_site("(64, 8)", "(64, 8)")) == []


def test_pg901_block_rank_vs_operand_rank():
    src = (
        _PG_PRELUDE
        + "def f():\n"
        "    x = jnp.zeros((256, 8, 4), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((64, 8), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((64, 8), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((256, 8), jnp.float32),\n"
        "    )(x)\n"
    )
    assert "PG901" in codes(src)


def test_pg902_window_overrun_at_grid_corner():
    # 4 grid steps of a 96-row block over 256 rows: corner i=3 ends at 384
    found = codes(_pg_site("(96, 8)", "(96, 8)"))
    assert "PG902" in found


def test_pg902_negative_exact_tiling():
    # 4 x 64 == 256: the corner window ends exactly at the boundary
    assert codes(_pg_site("(64, 8)", "(64, 8)")) == []


def test_pg902_intentional_clamp_is_reason_suppressed():
    src = (
        _PG_PRELUDE
        + "def f():\n"
        "    x = jnp.zeros((256, 8), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((96, 8), lambda i: (i, 0))],"
        "  # analysis: disable=PG902 index map clamps the tail block\n"
        "        out_specs=pl.BlockSpec((96, 8), lambda i: (i, 0)),"
        "  # analysis: disable=PG902 index map clamps the tail block\n"
        "        out_shape=jax.ShapeDtypeStruct((256, 8), jnp.float32),\n"
        "    )(x)\n"
    )
    vs = analyze_source(src)
    assert [v.code for v in vs if not v.suppressed] == []
    assert {v.code for v in vs if v.suppressed} == {"PG902"}
    assert all(v.reason for v in vs if v.suppressed)


def test_pg903_vmem_budget_exceeded():
    src = (
        _PG_PRELUDE
        + "def f():\n"
        "    x = jnp.zeros((8192, 8192), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((4096, 8192), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((4096, 8192), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((8192, 8192), jnp.float32),\n"
        "    )(x)\n"
    )
    assert "PG903" in codes(src)


def test_pg903_negative_fits_budget():
    # 2 x 64 x 8 x 4B = 4 KiB per grid step: far under 16 MiB
    assert codes(_pg_site("(64, 8)", "(64, 8)")) == []


def test_pg903_budget_is_tunable():
    from paddle_tpu.analysis.checkers.pallas_geometry import PallasGeometryChecker

    chk = PallasGeometryChecker()
    chk.vmem_budget = 1024  # 2 x 64 x 8 x 4B = 4096 > 1 KiB
    vs = analyze_source(_pg_site("(64, 8)", "(64, 8)"), checkers=[chk])
    assert "PG903" in {v.code for v in vs}


def _pg903_dtype_site(dtype: str) -> str:
    # one (512, 8192) block in + out: 4 MiB each at 1 byte/elt, 16 MiB each
    # at 4 bytes/elt — the SAME geometry crosses the 16 MiB budget purely on
    # the element width, so the audit must price narrow dtypes truthfully
    return (
        _PG_PRELUDE
        + "def f():\n"
        f"    x = jnp.zeros((8192, 8192), {dtype})\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(16,),\n"
        "        in_specs=[pl.BlockSpec((512, 8192), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((512, 8192), lambda i: (i, 0)),\n"
        f"        out_shape=jax.ShapeDtypeStruct((8192, 8192), {dtype}),\n"
        "    )(x)\n"
    )


def test_pg903_int8_true_width_fits_budget():
    """The quantized-kernel case (kernels/quant.py): an int8 window the
    audit would flag at an assumed 4-byte width fits comfortably at its TRUE
    1-byte width — narrow dtypes must not produce false PG903 positives."""
    assert codes(_pg903_dtype_site("jnp.int8")) == []


def test_pg903_fp8_true_width_fits_budget():
    assert codes(_pg903_dtype_site("jnp.float8_e4m3fn")) == []


def test_pg903_fp32_same_geometry_exceeds_budget():
    """Negative control for the pair above: the identical block geometry at
    4 bytes/elt crosses the 16 MiB budget — the dtype is the only delta."""
    assert "PG903" in codes(_pg903_dtype_site("jnp.float32"))


def _pg903_limit_site(compiler_params: str, helper: str = "") -> str:
    # the float32 site of _pg903_dtype_site (32 MiB a grid step, over the
    # 16 MiB default) with the site's own compiler params
    return (
        _PG_PRELUDE
        + "from jax.experimental.pallas import tpu as pltpu\n"
        + helper
        + "def f(need):\n"
        "    x = jnp.zeros((8192, 8192), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(16,),\n"
        f"        compiler_params={compiler_params},\n"
        "        in_specs=[pl.BlockSpec((512, 8192), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((512, 8192), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((8192, 8192), jnp.float32),\n"
        "    )(x)\n"
    )


_PG903_HELPER = (
    "def _params(need):\n"
    "    return pltpu.CompilerParams(\n"
    "        dimension_semantics=('parallel',), vmem_limit_bytes=need)\n"
)


@pytest.mark.parametrize(
    "compiler_params, helper, flagged",
    [
        # no limit stated: the default 16 MiB budget holds the 32 MiB window
        ("pltpu.CompilerParams(dimension_semantics=('parallel',))", "", True),
        ("pltpu.CompilerParams(vmem_limit_bytes=None)", "", True),
        # the site asks for 64 MiB: that is its budget (kernels/fused_loss.py)
        ("pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)", "", False),
        ("pltpu.CompilerParams(vmem_limit_bytes=64 << 20)", "", False),
        # ... and is held to it
        ("pltpu.CompilerParams(vmem_limit_bytes=24 << 20)", "", True),
        # a limit derived from runtime shapes, here or in the local helper
        # that builds the params: stated, nothing to hold the window to
        ("pltpu.CompilerParams(vmem_limit_bytes=need)", "", False),
        ("_params(need)", _PG903_HELPER, False),
    ],
    ids=["none_stated", "none_literal", "own_limit_product", "own_limit_shift",
         "over_own_limit", "runtime_limit", "helper_built"],
)
def test_pg903_reads_the_sites_own_vmem_limit(compiler_params, helper, flagged):
    """A site that states ``vmem_limit_bytes`` is judged by that limit, not by
    Mosaic's 16 MiB default."""
    found = "PG903" in codes(_pg903_limit_site(compiler_params, helper))
    assert found == flagged


def test_pg903_own_limit_is_the_budget_in_the_message():
    vs = analyze_source(_pg903_limit_site("pltpu.CompilerParams(vmem_limit_bytes=24 << 20)"))
    (v,) = [v for v in vs if v.code == "PG903"]
    assert f"budget {24 << 20}" in v.message


def test_pg903_int8_width_not_assumed():
    """int8 is a KNOWN width (DTYPE_BYTES), not the assumed-1-byte fallback:
    the VMEM config must not carry the ``assumed_width`` caveat."""
    from paddle_tpu.analysis.kernel_geometry import DTYPE_BYTES, evaluate_module
    import ast

    assert DTYPE_BYTES["int8"] == 1
    assert DTYPE_BYTES["float8_e4m3fn"] == 1
    src = _pg903_dtype_site("jnp.int8")
    mod = evaluate_module("x.py", ast.parse(src))
    sites = mod.sites
    assert sites, "fixture must contain a pallas_call site"
    for site in sites:
        for vc in site.vmem_configs:
            assert not vc.assumed_width


def test_pg_sweep_quant_kernel_clean():
    """The weight-only int8 kernel ships PG-clean: a full checker sweep over
    kernels/quant.py (geometry, prefetch, dispatch discipline) reports zero
    unsuppressed violations."""
    vs = analyze_paths([str(PKG / "kernels" / "quant.py")])
    bad = [v for v in vs if not v.suppressed]
    assert bad == [], [f"{v.code}:{v.line}" for v in bad]


@pytest.mark.parametrize(
    "kernel, operands",
    [
        # (in_specs, out_specs, out_shapes, scratch) as the site declares them
        ("_flxent_dx_kernel", (5, 1, 1, 1)),  # x, W, lab, lse, gcoef -> dX
        ("_flxent_dx_store_kernel", (5, 2, 2, 1)),  # ... -> dX and the d tile
        ("_flxent_dw_kernel", (2, 1, 1, 1)),  # x and the stored d -> dW
        ("_flxent_dw_recompute_kernel", (5, 1, 1, 1)),  # x, W, lab, lse, gcoef -> dW
    ],
)
def test_pg_loss_head_backward_sites_follow_their_operand_lists(kernel, operands):
    """The fused loss head's backward is two pairs of sites since PR 37 (dX
    with or without the ``d`` output, dW over the stored ``d`` or recomputing
    it): the geometry evaluator resolves each site's lists, the kernel's
    positional refs are exactly in + out + scratch (PG901's count, proven and
    not ``unproven``), and the file sweeps PG-clean."""
    import ast

    from paddle_tpu.analysis.kernel_geometry import evaluate_module

    path = PKG / "kernels" / "fused_loss.py"
    mod = evaluate_module(str(path), ast.parse(path.read_text()))
    (site,) = [s for s in mod.sites if s.kernel_name == kernel]
    assert (len(site.in_specs), len(site.out_specs), site.n_out_shapes, site.n_scratch) == operands
    assert site.out_specs_declared and not site.has_vararg
    assert len(site.kernel_params) == operands[0] + operands[1] + operands[3]
    bad = [v for v in analyze_paths([str(path)], select=["PG"]) if not v.suppressed]
    assert bad == [], [f"{v.code}:{v.line}" for v in bad]


_PG_PREFETCH = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from jax.experimental import pallas as pl\n"
    "from jax.experimental.pallas import tpu as pltpu\n"
    "def k(ids_ref, x_ref, o_ref):\n"
    "    o_ref[...] = x_ref[...]\n"
)


def _pg_prefetch_site(map_in):
    return (
        _PG_PREFETCH
        + "def f(x, ids):\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid_spec=pltpu.PrefetchScalarGridSpec(\n"
        "            num_scalar_prefetch=1,\n"
        "            grid=(4,),\n"
        f"            in_specs=[pl.BlockSpec((8, 8), {map_in})],\n"
        "            out_specs=pl.BlockSpec((8, 8), lambda i, ids: (i, 0)),\n"
        "        ),\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 8), jnp.float32),\n"
        "    )(ids, x)\n"
    )


def test_pg904_prefetch_ref_indexed_by_non_grid_value():
    found = codes(_pg_prefetch_site("lambda i, ids: (ids[j], 0)"))
    assert "PG904" in found


def test_pg904_negative_grid_indexed_prefetch():
    assert codes(_pg_prefetch_site("lambda i, ids: (ids[i], 0)")) == []


def test_pg904_prefetch_arity_mismatch():
    # index maps take grid rank + num_scalar_prefetch args; one short fires
    found = codes(_pg_prefetch_site("lambda i: (i, 0)"))
    assert "PG904" in found


def test_pg905_gated_dispatch_without_fallback_counter():
    src = (
        "from paddle_tpu.kernels.select import pallas_enabled\n"
        "def dispatch(x):\n"
        "    if pallas_enabled('use_pallas_paged_attention'):\n"
        "        return fast_kernel(x)\n"
        "    return slow_path(x)\n"
    )
    assert "PG905" in codes(src)


def test_pg905_negative_warn_fallback_registered():
    src = (
        "from paddle_tpu.kernels.select import pallas_enabled, warn_fallback\n"
        "def dispatch(x):\n"
        "    if pallas_enabled('use_pallas_paged_attention'):\n"
        "        try:\n"
        "            return fast_kernel(x)\n"
        "        except Exception as exc:"
        "  # analysis: disable=EH403 fixture: XLA fallback below\n"
        "            warn_fallback('fast_kernel', exc)\n"
        "    return slow_path(x)\n"
    )
    assert codes(src) == []


def test_pg905_public_kernel_entry_needs_coverage():
    # a public pallas_call-lowering entry in kernels/ nobody fallback-wraps
    src = _pg_site("(64, 8)", "(64, 8)").replace("def f():", "def public_kernel():")
    found = codes(src, path="paddle_tpu/kernels/pg_snippet.py")
    assert "PG905" in found
    # the same module-private entry is some wrapper's implementation detail
    src_private = _pg_site("(64, 8)", "(64, 8)").replace("def f():", "def _impl():")
    assert codes(src_private, path="paddle_tpu/kernels/pg_snippet.py") == []


def test_pg905_self_wrapping_entry_is_covered():
    src = (
        _PG_PRELUDE
        + "from paddle_tpu.kernels.select import warn_fallback\n"
        "def public_kernel():\n"
        "    x = jnp.zeros((256, 8), jnp.float32)\n"
        "    try:\n"
        "        return pl.pallas_call(\n"
        "            k,\n"
        "            grid=(4,),\n"
        "            in_specs=[pl.BlockSpec((64, 8), lambda i: (i, 0))],\n"
        "            out_specs=pl.BlockSpec((64, 8), lambda i: (i, 0)),\n"
        "            out_shape=jax.ShapeDtypeStruct((256, 8), jnp.float32),\n"
        "        )(x)\n"
        "    except Exception as exc:"
        "  # analysis: disable=EH403 fixture: XLA fallback below\n"
        "        warn_fallback('public_kernel', exc)\n"
        "    return x\n"
    )
    assert codes(src, path="paddle_tpu/kernels/pg_snippet.py") == []


# -- kernel_geometry resolution edge cases -----------------------------------

def _geom(src, path="geom_snippet.py"):
    import ast as _ast

    from paddle_tpu.analysis.kernel_geometry import evaluate_module

    return evaluate_module(path, _ast.parse(src))


def test_geometry_autotune_candidates_and_cdiv_grid():
    """Block sizes flowing from autotune candidate tuples stay correlated
    per configuration (a ``pl.cdiv`` grid derived from the same candidate),
    so a bad candidate is named concretely instead of smearing every
    config to unproven."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "from paddle_tpu.kernels.autotune import autotune\n"
        "ROWS = 256\n"
        "def k(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def build(blk):\n"
        "    x = jnp.zeros((ROWS, 8), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(pl.cdiv(ROWS, blk),),\n"
        "        in_specs=[pl.BlockSpec((blk, 8), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((blk, 8), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((ROWS, 8), jnp.float32),\n"
        "    )(x)\n"
        "impl = autotune('thing', 'key', (64, 96), build, default=64)\n"
    )
    site = _geom(src).sites[0]
    # cdiv folded per candidate: 256/64 -> 4 steps, 256/96 -> 3 steps
    assert site.grid[0].values == frozenset({3, 4})
    # the 96 candidate's last block ends at 288 > 256 — named, not smeared
    overruns = [p for p in site.axis_proofs if p.status == "overrun"]
    assert overruns and all("blk=96" in p.detail for p in overruns)
    # VMEM footprint tracked per candidate config (in + out, f32)
    per_cfg = {
        cfg.binding["blk"]: cfg.bytes_per_step.concrete()
        for cfg in site.vmem_configs
    }
    assert per_cfg == {64: 2 * 64 * 8 * 4, 96: 2 * 96 * 8 * 4}


def test_geometry_named_index_map_function():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "def _row_map(i):\n"
        "    return (i, 0)\n"
        "def k(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def f():\n"
        "    x = jnp.zeros((256, 8), jnp.float32)\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((64, 8), _row_map)],\n"
        "        out_specs=pl.BlockSpec((64, 8), _row_map),\n"
        "        out_shape=jax.ShapeDtypeStruct((256, 8), jnp.float32),\n"
        "    )(x)\n"
    )
    site = _geom(src).sites[0]
    spec = site.in_specs[0]
    assert spec.map_params == ["i"] and spec.ret_arity == 2
    assert {p.status for p in site.axis_proofs} == {"proven"}


def test_geometry_symbolic_grid_axis_is_unproven_not_passed():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "def k(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def f(x, n):\n"
        "    return pl.pallas_call(\n"
        "        k,\n"
        "        grid=(n // 64,),\n"
        "        in_specs=[pl.BlockSpec((64, 8), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((64, 8), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((256, 8), jnp.float32),\n"
        "    )(x)\n"
    )
    site = _geom(src).sites[0]
    assert not site.grid[0].known  # symbolic residue, honestly reported
    dim0 = [p for p in site.axis_proofs if p.dim == 0]
    assert dim0 and {p.status for p in dim0} == {"unproven"}
    # unproven is NOT a finding — but it is never silently "proven" either
    assert "PG902" not in codes(src)


def test_geometry_is_memoized_in_package_index():
    """The PG layer rides the PR 9 memoization contract: one evaluation per
    module per PackageIndex, however many checkers ask."""
    import ast as _ast

    from paddle_tpu.analysis import dataflow as _df

    idx = _df.PackageIndex()
    tree = _ast.parse(_pg_site("(64, 8)", "(64, 8)"))
    idx.add_module("geom_memo.py", tree)
    g1 = idx.kernel_geometry("geom_memo.py")
    g2 = idx.kernel_geometry("geom_memo.py")
    assert g1 is g2 and len(g1.sites) == 1


# -- CM: distributed protocol -------------------------------------------------

def test_cm1001_rank_divergent_collective():
    assert "CM1001" in codes(
        "import paddle_tpu.distributed as dist\n"
        "import jax\n"
        "def sync(x):\n"
        "    rank = jax.process_index()\n"
        "    if rank == 0:\n"
        "        dist.broadcast(x, src=0)\n",
        select=["CM"],
    )


def test_cm1001_negative_rejoin_after_branch():
    """The branch touches rank-local state but EVERY rank reaches the
    collective afterwards — the canonical checkpoint-then-sync shape."""
    assert codes(
        "import paddle_tpu.distributed as dist\n"
        "import jax\n"
        "def sync(x):\n"
        "    rank = jax.process_index()\n"
        "    if rank == 0:\n"
        "        x = x + 1\n"
        "    dist.broadcast(x, src=0)\n",
        select=["CM"],
    ) == []


def test_cm1001_negative_balanced_arms():
    """Both arms issue the same collective: every rank participates
    whichever way the rank test goes."""
    assert codes(
        "import paddle_tpu.distributed as dist\n"
        "import jax\n"
        "def sync(x, y):\n"
        "    rank = jax.process_index()\n"
        "    if rank == 0:\n"
        "        dist.broadcast(x, src=0)\n"
        "    else:\n"
        "        dist.broadcast(y, src=0)\n",
        select=["CM"],
    ) == []


def test_cm1002_collective_under_thread_shared_lock():
    assert "CM1002" in codes(
        "import threading\n"
        "import paddle_tpu.distributed as dist\n"
        "class Manager:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._probe_loop)\n"
        "    def _probe_loop(self):\n"
        "        with self._lock:\n"
        "            self._n = 1\n"
        "    def sync(self, x):\n"
        "        with self._lock:\n"
        "            dist.all_reduce(x)\n",
        select=["CM"],
    )


def test_cm1002_negative_lock_not_thread_shared():
    assert codes(
        "import threading\n"
        "import paddle_tpu.distributed as dist\n"
        "class Manager:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def sync(self, x):\n"
        "        with self._lock:\n"
        "            dist.all_reduce(x)\n",
        select=["CM"],
    ) == []


def test_cm1003_counter_key_without_delete():
    """Minimized ``all_gather_object`` replica: a per-call counter namespaces
    the store key, so every call strands a fresh key forever unless a
    dominating delete reclaims it (the unbounded-store failure)."""
    assert "CM1003" in codes(
        "_calls = [0]\n"
        "def gather(client, rank, payload):\n"
        "    n = _calls[0]\n"
        "    _calls[0] += 1\n"
        "    prefix = f\"gather/{n}\"\n"
        "    client.key_value_set(f\"{prefix}/{rank}\", payload)\n",
        select=["CM"],
    )


def test_cm1003_negative_finally_deleted_counter_key():
    assert codes(
        "_calls = [0]\n"
        "def gather(client, rank, payload):\n"
        "    n = _calls[0]\n"
        "    _calls[0] += 1\n"
        "    prefix = f\"gather/{n}\"\n"
        "    try:\n"
        "        client.key_value_set(f\"{prefix}/{rank}\", payload)\n"
        "    finally:\n"
        "        client.key_value_delete(f\"{prefix}/{rank}\")\n",
        select=["CM"],
    ) == []


def test_cm1004_collective_in_except_arm():
    assert "CM1004" in codes(
        "import paddle_tpu.distributed as dist\n"
        "def step(x):\n"
        "    try:\n"
        "        y = x.compute()\n"
        "    except ValueError:\n"
        "        dist.barrier()\n",
        select=["CM"],
    )


def test_cm1004_negative_try_body_cannot_raise():
    assert codes(
        "import paddle_tpu.distributed as dist\n"
        "def step(x):\n"
        "    try:\n"
        "        y = 1\n"
        "    except ValueError:\n"
        "        dist.barrier()\n",
        select=["CM"],
    ) == []


def test_cm1005_partition_spec_axis_outside_mesh():
    assert "CM1005" in codes(
        "import numpy as np\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "mesh = Mesh(np.array([]), (\"dp\", \"tp\"))\n"
        "def spec():\n"
        "    return P(\"model\")\n",
        select=["CM"],
    )


def test_cm1005_negative_axis_in_mesh_universe():
    assert codes(
        "import numpy as np\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "mesh = Mesh(np.array([]), (\"dp\", \"tp\"))\n"
        "def spec():\n"
        "    return P(\"tp\", None)\n",
        select=["CM"],
    ) == []


def test_cm1005_donating_jit_without_out_shardings():
    assert "CM1005" in codes(
        "import jax\n"
        "def build(fn, shardings):\n"
        "    return jax.jit(fn, donate_argnums=(1,), in_shardings=shardings)\n",
        select=["CM"],
    )


def test_cm1005_negative_out_shardings_pinned():
    assert codes(
        "import jax\n"
        "def build(fn, shardings):\n"
        "    return jax.jit(fn, donate_argnums=(1,), in_shardings=shardings,\n"
        "                   out_shardings=shardings)\n",
        select=["CM"],
    ) == []


def test_cm_protocol_calls_memoized_in_package_index():
    """CM rides the PR 9 memoization contract like PG: the module graph (and
    its recorded protocol calls) is built once per PackageIndex, however
    many checkers ask for it."""
    import ast as _ast

    from paddle_tpu.analysis import dataflow as _df

    idx = _df.PackageIndex()
    tree = _ast.parse(
        "import paddle_tpu.distributed as dist\n"
        "def f(x):\n"
        "    dist.all_reduce(x)\n"
    )
    idx.add_module("cm_memo.py", tree)
    g1 = idx.module("cm_memo.py")
    g2 = idx.module("cm_memo.py")
    assert g1 is g2
    assert [p.op for p in g1.protocol_calls if p.kind == "collective"] == ["all_reduce"]
    # the thread-acquirer closure is memoized too (CM1002's partner set)
    a1 = idx.thread_lock_acquirers()
    a2 = idx.thread_lock_acquirers()
    assert a1 is a2


def test_cm_baseline_accepts_known_finding(tmp_path):
    """A baselined CM finding stops gating; a new one past the baseline
    gates again — same contract as every other family."""
    bad = tmp_path / "proto.py"
    bad.write_text(
        "import paddle_tpu.distributed as dist\n"
        "def step(x):\n"
        "    try:\n"
        "        y = x.compute()\n"
        "    except ValueError:\n"
        "        dist.barrier()\n"
    )
    r = _run_cli(["--select", "CM", str(bad)])
    assert r.returncode == 1 and "CM1004" in r.stdout
    base = tmp_path / "base.json"
    r = _run_cli(["--select", "CM", "--write-baseline", str(base), str(bad)])
    assert r.returncode == 0
    r = _run_cli(["--select", "CM", "--baseline", str(base), str(bad)])
    assert r.returncode == 0
    bad.write_text(
        bad.read_text()
        + "def step2(x):\n"
        "    try:\n"
        "        y = x.compute()\n"
        "    except ValueError:\n"
        "        dist.barrier()\n"
    )
    r = _run_cli(["--select", "CM", "--baseline", str(base), str(bad)])
    assert r.returncode == 1


def test_timings_flag_names_every_checker_and_phase(tmp_path):
    """--timings must attribute the 30s budget: one ``checker:`` line per
    registered checker (zero-cost ones included) and the index phases."""
    f = tmp_path / "ok.py"
    f.write_text("import paddle_tpu.distributed as dist\ndef f(x):\n    dist.all_reduce(x)\n")
    r = _run_cli(["--timings", str(f)])
    assert r.returncode == 0
    assert "timings:" in r.stderr
    for checker in all_checkers():
        assert f"checker {checker.name}" in " ".join(r.stderr.split()), (
            f"--timings output missing checker {checker.name!r}:\n{r.stderr}"
        )
    assert "phase" in r.stderr and "parse" in r.stderr


# -- SARIF + baseline ---------------------------------------------------------

def test_sarif_output_shape_and_rule_ids():
    from paddle_tpu.analysis import all_codes as _codes
    from paddle_tpu.analysis.reporters import render_sarif

    vs = analyze_source(
        "try:\n"
        "    f()\n"
        "except:\n"
        "    pass\n"
        "try:\n"
        "    g()\n"
        "except:  # analysis: disable=EH401 fixture accepts this one\n"
        "    pass\n"
    )
    doc = json.loads(render_sarif(vs, _codes()))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "EH401" in rules and "CC701" in rules and "DN802" in rules
    # the PG family rides the same schema: rule ids only, no shape change
    assert {"PG901", "PG902", "PG903", "PG904", "PG905"} <= rules
    # the CM family too
    assert {"CM1001", "CM1002", "CM1003", "CM1004", "CM1005"} <= rules
    results = run["results"]
    live = [r for r in results if "suppressions" not in r]
    sup = [r for r in results if "suppressions" in r]
    assert len(live) >= 1 and len(sup) == 1
    assert sup[0]["suppressions"][0]["justification"] == "fixture accepts this one"
    loc = live[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] >= 1 and loc["region"]["startColumn"] >= 1


def test_baseline_accepts_known_and_catches_new(tmp_path):
    from paddle_tpu.analysis.reporters import (
        load_baseline,
        new_violations,
        write_baseline,
    )

    one = analyze_source("try:\n    f()\nexcept:\n    pass\n")
    base = tmp_path / "base.json"
    write_baseline(str(base), one)
    known = load_baseline(str(base))
    # same findings: nothing new
    assert new_violations(one, known) == []
    # a second bare except in the same file is NEW (count-based fingerprints)
    two = analyze_source(
        "try:\n    f()\nexcept:\n    pass\n"
        "try:\n    g()\nexcept:\n    pass\n"
    )
    fresh = new_violations(two, known)
    assert len(fresh) == 1 and fresh[0].code in ("EH401",)


def test_baseline_rejects_wrong_shape(tmp_path):
    from paddle_tpu.analysis.reporters import load_baseline

    bad = tmp_path / "bad.json"
    bad.write_text('{"findings": {"a": 1}}')
    with pytest.raises(ValueError):
        load_baseline(str(bad))


def test_cli_sarif_and_baseline_gate(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    f()\nexcept:\n    pass\n")
    r = _run_cli(["--format", "sarif", str(bad)])
    assert r.returncode == 1
    assert json.loads(r.stdout)["version"] == "2.1.0"
    base = tmp_path / "base.json"
    r = _run_cli(["--write-baseline", str(base), str(bad)])
    assert r.returncode == 0 and base.exists()
    # baselined: the known finding no longer gates
    r = _run_cli(["--baseline", str(base), str(bad)])
    assert r.returncode == 0
    # a NEW finding past the baseline count gates again
    bad.write_text(
        "try:\n    f()\nexcept:\n    pass\n"
        "try:\n    g()\nexcept:\n    pass\n"
    )
    r = _run_cli(["--baseline", str(base), str(bad)])
    assert r.returncode == 1
    # a corrupt baseline must not turn the gate vacuous
    base.write_text("not json")
    r = _run_cli(["--baseline", str(base), str(bad)])
    assert r.returncode == 2


# -- CI perf gate: one memoized dataflow pass, bounded wall time --------------

def test_analyzer_wall_time_and_single_dataflow_pass():
    """The tier-1 gate runs every checker family over the whole package; the
    dataflow graphs must be built once per module (memoized in the
    PackageIndex) and the whole run must stay under 30 s — including the
    interprocedural CM family, which must ride the shared index rather
    than build its own."""
    import time as _time

    from paddle_tpu.analysis import dataflow as _df

    # the budget is only meaningful if the expensive families are actually in
    # the run — guard against the gate going vacuous via deregistration
    names = {c.name for c in all_checkers()}
    assert {"distributed_protocol", "pallas_geometry", "concurrency"} <= names

    builds = {"n": 0}
    orig = _df.ModuleGraph._build

    def counting_build(self):
        builds["n"] += 1
        return orig(self)

    _df.ModuleGraph._build = counting_build
    try:
        t0 = _time.perf_counter()
        vs = analyze_paths([str(PKG)])
        dt = _time.perf_counter() - t0
    finally:
        _df.ModuleGraph._build = orig
    n_modules = len(list(PKG.rglob("*.py")))
    assert builds["n"] <= n_modules, (
        f"dataflow graphs rebuilt: {builds['n']} builds for {n_modules} modules"
    )
    assert dt < 30.0, f"whole-package analysis took {dt:.1f}s (budget 30s)"
    assert isinstance(vs, list)


# -- the tier-1 gate: the package must analyze clean -------------------------

def test_whole_package_clean():
    vs = analyze_paths([str(PKG)])
    live = [v for v in vs if not v.suppressed]
    assert live == [], "unsuppressed violations:\n" + "\n".join(v.format() for v in live)
    # acceptance: every suppression carries a reason string
    for v in vs:
        if v.suppressed:
            assert v.reason, v.format()


def test_cli_whole_package_gate():
    r = _run_cli(["--format", "json", "paddle_tpu/"])
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(r.stdout)
    assert data["summary"]["unsuppressed"] == 0


# -- flags satellite: env-coercion failures name the flag --------------------

def test_env_coercion_error_names_flag_and_env_var(monkeypatch):
    from paddle_tpu.flags import FlagRegistry

    reg = FlagRegistry()
    reg.define("scan_depth", int, 4)
    monkeypatch.setenv("FLAGS_scan_depth", "not-an-int")
    with pytest.raises(ValueError) as ei:
        reg.get("scan_depth")
    msg = str(ei.value)
    assert "FLAGS_scan_depth" in msg and "scan_depth" in msg and "int" in msg
    # the error re-fires on every read — a first get() swallowed by someone's
    # broad except must not leave the flag silently serving its default
    with pytest.raises(ValueError):
        reg.get("scan_depth")


def test_set_coercion_error_names_flag():
    from paddle_tpu.flags import FlagRegistry

    reg = FlagRegistry()
    reg.define("scan_depth", int, 4)
    with pytest.raises(ValueError, match="scan_depth"):
        reg.set("scan_depth", "nope")
