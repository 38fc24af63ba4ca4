"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's "distributed tests without a cluster" strategy
(SURVEY §4): the reference spawns localhost NCCL subprocesses; on TPU/XLA the
CPU backend natively exposes N virtual devices, so multi-device SPMD tests run
in-process.
"""

import os
import sys

# Force the CPU backend: the tests are the CPU rehearsal (the chip run is
# chip_smoke.py's job). Set in the environment for child processes and in
# jax.config for this one, in case jax was imported before conftest.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# entry points under test (start_serving_server) turn the persistent compile
# cache on; a test run must neither write into the checkout nor run faster
# the second time (the failover races are timed against real compiles)
jax.config.update("jax_enable_compilation_cache", False)
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The CPU backend's "default" matmul precision truncates to bf16-class
# accuracy; tests compare against numpy fp32 references.
jax.config.update("jax_default_matmul_precision", "highest")

@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
    # isolate global mesh state between tests (set_mesh leaks otherwise)
    import paddle_tpu.distributed.mesh as _mesh

    _mesh._global_mesh = None


@pytest.fixture
def scan_kernel_route(monkeypatch):
    """``RecurrentState.advance`` on the route it takes on a TPU: the dispatch
    of ``mamba2.ssd_chunk_slots`` is told yes for the state-space scan's
    kernel (and for no other kernel), and ``kernels/ssm_scan.py`` runs in
    Pallas' interpreter. Yields the list of state-plane shapes the kernel was
    traced with; the route has to have been taken, and without a fallback."""
    import functools

    from paddle_tpu.kernels import select, ssm_scan

    traced, scan = [], ssm_scan.ssm_state_scan

    def interpreted(*args, **kwargs):
        traced.append(tuple(args[4].shape))
        return scan(*args, interpret=True, **kwargs)

    monkeypatch.setattr(ssm_scan, "ssm_state_scan", functools.wraps(scan)(interpreted))
    monkeypatch.setattr(select, "pallas_enabled", lambda flag, bare=None, row_wise=False: bare == ssm_scan.KERNEL_SCAN)
    before = dict(select.fallback_counts())
    yield traced
    assert traced, "the kernel route was not taken"
    assert dict(select.fallback_counts()) == before  # and it did not degrade to the XLA composition


def assert_engine_pool_exact(eng):
    """The engine pool-accounting churn invariant, shared by every engine
    suite (engine / spec-decode / prefix-cache / tp): refcount truth —
    every refcounted block's owner count equals its live mappings (slot
    tables + pending CoW pins) plus cache chain ownership — exact
    allocated+free accounting, no live table referencing a freed block,
    and the cached chain aligned as a prefix of each slot's block table."""
    s = eng.pool_stats()
    assert s["allocated"] + s["free"] == s["total"], s
    expect = {}
    for slot, req in enumerate(eng._slot_req):
        if req is not None:
            for b in eng._blocks[slot]:
                expect[b] = expect.get(b, 0) + 1
    for pending in eng._pending_cow:
        if pending is not None:
            expect[pending[0].block] = expect.get(pending[0].block, 0) + 1
    if eng._cache is not None:
        for node in eng._cache._nodes.values():
            expect[node.block] = expect.get(node.block, 0) + 1
    assert eng._mgr.refcounts() == expect
    free = set(eng._mgr._free)
    for slot, req in enumerate(eng._slot_req):
        if req is not None:
            assert not (set(eng._blocks[slot]) & free), (
                f"slot {slot} references freed blocks"
            )
            for i, node in enumerate(eng._nodes[slot]):
                assert eng._blocks[slot][i] == node.block


def assert_kv_tier_exact(eng):
    """The hierarchical-KV churn invariant, shared by the tier suites:
    host-tier bytes stay within budget (and equal blocks x block_nbytes),
    and no block is live in BOTH tiers under the same chain key with
    mismatched contents — a device-resident chain node whose key also
    lives in the host tier must hold byte-identical KV (content-addressed
    immutability is what makes dual residency safe)."""
    import numpy as np

    tier = eng._host_tier
    if tier is None:
        return
    s = tier.stats_snapshot()
    assert s["host_bytes"] <= s["budget_bytes"], s
    assert s["host_bytes"] == len(tier) * tier.block_nbytes, s
    if eng._cache is None:
        return
    for node in list(eng._cache._nodes.values()):
        host = tier._entries.get(node.key)
        if host is None:
            continue
        assert host.digest == node.digest
        dev = eng._capture_block_kv(node.block)
        assert np.array_equal(np.asarray(dev), np.asarray(host.kv)), (
            f"block {node.block} resident in both tiers with mismatched KV"
        )
