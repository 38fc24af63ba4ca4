"""Cluster-scale serving: the prefix-affinity replica router.

The acceptance surface of ``serving/router.py`` + ``serving/cluster.py``:

- rendezvous hashing's minimal-remap property and the prefix-chain routing
  key's equality with the prefix cache's rolling digest;
- prefix affinity as a measurable property — a shared-prefix workload
  computes fewer prompt tokens and sees faster warm TTFT through affinity
  routing than through round-robin, and the affinity/spill/failover
  counters reconcile with the routing log exactly;
- replica death as a routing event: salvage, bounded deadline-aware
  re-dispatch, explicit terminals, terminal-exactly-once across failovers
  (the seeded churn property test and the kill-mid-storm acceptance test);
- drain semantics, health-probe fault degradation, flight-recorder state
  transitions, the ``router.failover`` trace span, and the all-replicas-dead
  black-box dump.

Everything runs on CPU with the tiny Llama config, same as test_serving.py.
Replicas share one model object (read-only at inference): identical weights
are what makes failover re-generation deterministic.
"""

import http.client
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.prefix_cache import PrefixCache, chain_digest
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    Overloaded,
    Priority,
    ReplicaCluster,
    ReplicaRouter,
    RouterConfig,
    ServingConfig,
    ServingFrontend,
    start_serving_server,
    stop_serving_server,
)
from paddle_tpu.serving.cluster import (
    REPLICA_DEAD,
    REPLICA_DEGRADED,
    REPLICA_DRAINING,
    REPLICA_UP,
)
from paddle_tpu.serving.loadgen import (
    TrafficClass,
    measure_sustainable_rate,
    poisson_arrivals,
    run_cluster_open_loop,
)
from paddle_tpu.serving.router import rendezvous_rank
from paddle_tpu.testing import faults


def _model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _cluster(seed=0, n=3, max_queue=8, router_cfg=None, **engine_kw):
    m, cfg = _model(seed)
    engine_kw.setdefault("max_slots", 2)
    engine_kw.setdefault("block_size", 4)
    engine_kw.setdefault("prompt_bucket", 16)

    def factory(name):
        eng = ContinuousBatchingEngine(m, **engine_kw)
        return ServingFrontend(eng, ServingConfig(max_queue=max_queue))

    cluster = ReplicaCluster(factory, [f"r{i}" for i in range(n)])
    router = ReplicaRouter(cluster, router_cfg or RouterConfig())
    return router, cluster, cfg


def _prompt(rng, cfg, n=6):
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


def _drain_router(router, handles, max_iters=800):
    done = []
    for _ in range(max_iters):
        done += router.pump()
        if all(h.finished for h in handles):
            return done
    raise AssertionError(
        "requests did not reach a terminal state: "
        f"{[(h.id, h.outcome, h.replica) for h in handles]} {router.snapshot()}"
    )


# -- routing key + rendezvous hashing -----------------------------------------

class TestRoutingKey:
    def test_rendezvous_minimal_remap_on_loss(self):
        names = ["a", "b", "c", "d"]
        keys = [bytes([i, i + 1]) for i in range(64)]
        owner = {k: rendezvous_rank(k, names)[0] for k in keys}
        survivors = [n for n in names if n != "b"]
        for k in keys:
            new = rendezvous_rank(k, survivors)[0]
            if owner[k] != "b":
                # only the dead replica's share remaps — the survivors'
                # keys (and so their warm caches) are untouched
                assert new == owner[k]
            else:
                assert new in survivors

    def test_rendezvous_add_only_steals(self):
        names = ["a", "b", "c"]
        keys = [bytes([i]) for i in range(64)]
        owner = {k: rendezvous_rank(k, names)[0] for k in keys}
        grown = names + ["d"]
        stolen = 0
        for k in keys:
            new = rendezvous_rank(k, grown)[0]
            if new != owner[k]:
                assert new == "d"  # a new replica only ever takes, never shuffles
                stolen += 1
        assert 0 < stolen < len(keys)

    def test_prefix_chain_hash_matches_cache_digest_recurrence(self):
        m, cfg = _model(seed=1)
        eng = ContinuousBatchingEngine(m, max_slots=1, block_size=4, prompt_bucket=16)
        prompt = np.arange(10, dtype=np.int32)
        # the engine's routing key walks the same H(parent, tokens) chain
        # the prefix cache keys nodes by
        d = b"prefix-cache-root"
        for i in range(2):  # two full blocks of 4
            d = PrefixCache._digest(d, prompt[i * 4 : (i + 1) * 4].tobytes())
        assert eng.prefix_chain_hash(prompt) == d.hex()
        # capping at one block matches the one-block walk
        d1 = PrefixCache._digest(b"prefix-cache-root", prompt[:4].tobytes())
        assert eng.prefix_chain_hash(prompt, max_blocks=1) == d1.hex()

    def test_shared_prefix_same_key_divergent_tails(self):
        shared = np.arange(8, dtype=np.int32)
        a = np.concatenate([shared, np.asarray([90, 91, 92], np.int32)])
        b = np.concatenate([shared, np.asarray([70, 71], np.int32)])
        ka = chain_digest(a, 4, max_blocks=2)
        kb = chain_digest(b, 4, max_blocks=2)
        assert ka == kb  # tails beyond the affinity window do not scatter
        # ... but different prefixes do spread
        c = np.concatenate([shared + 1, np.asarray([90], np.int32)])
        assert chain_digest(c, 4, max_blocks=2) != ka
        # short prompts hash raw tokens (still spread, never collide to root)
        assert chain_digest(np.asarray([1, 2], np.int32), 4) != chain_digest(
            np.asarray([3], np.int32), 4
        )


# -- affinity routing ----------------------------------------------------------

class TestAffinityRouting:
    def test_shared_prefix_lands_on_one_replica_and_counters_reconcile(self):
        router, cluster, cfg = _cluster(seed=2)
        rng = np.random.default_rng(2)
        shared = _prompt(rng, cfg, 8)
        handles = []
        for _ in range(5):
            tail = _prompt(rng, cfg, 3)
            handles.append(
                router.submit(np.concatenate([shared, tail]), max_new_tokens=2)
            )
        owners = {h.replica for h in handles}
        assert len(owners) == 1  # one family, one replica
        _drain_router(router, handles)
        assert all(h.outcome == "ok" for h in handles)
        counters = router.routing_counters()
        assert counters["affinity"] == 5
        # reconciliation: every routing decision is one count + one log entry
        # (the log is a bounded window; the monotonic dispatch count is the
        # reconciliation surface)
        assert sum(counters.values()) == router.dispatch_count() == 5
        assert len(router.routing_log()) == 5

    def test_affinity_beats_round_robin_on_shared_prefix_workload(self):
        """ISSUE acceptance: prefix affinity is measurable. The same
        3-family shared-prefix workload through affinity routing vs
        round-robin: affinity computes fewer prompt tokens (each family's
        prefix computed once cluster-wide vs once per replica), shows a
        higher prefix-cache hit rate, and its warm requests see faster
        TTFT. Requests run one at a time so TTFT is step-count, not
        batching noise."""
        results = {}
        for policy in ("affinity", "round_robin"):
            router, cluster, cfg = _cluster(
                seed=3, router_cfg=RouterConfig(policy=policy)
            )
            rng = np.random.default_rng(3)  # same workload both ways
            families = [_prompt(rng, cfg, 8) for _ in range(3)]
            warm_ttfts = []
            seen_family = set()
            for i in range(18):
                # seeded family choice (NOT i % n_replicas: that would
                # accidentally align round-robin's rotation with the
                # families and hand it perfect affinity)
                fam = int(rng.integers(0, 3))
                prompt = np.concatenate(
                    [families[fam], _prompt(rng, cfg, 3)]
                )
                h = router.submit(prompt, max_new_tokens=2)
                _drain_router(router, [h])
                assert h.outcome == "ok"
                if fam in seen_family:
                    warm_ttfts.append(h.first_token_time - h.submit_time)
                seen_family.add(fam)
            computed = sum(
                r.frontend.engine.stats["prompt_tokens_computed"]
                for r in cluster
            )
            reused = sum(
                r.frontend.engine.stats["prompt_tokens_reused"]
                for r in cluster
            )
            results[policy] = {
                "computed": computed,
                "reused": reused,
                "warm_ttft_mean": sum(warm_ttfts) / len(warm_ttfts),
                "routes": router.routing_counters(),
                "log": len(router.routing_log()),
            }
        aff, rr = results["affinity"], results["round_robin"]
        # every routing decision accounted, both policies
        assert sum(aff["routes"].values()) == aff["log"] == 18
        assert rr["routes"]["round_robin"] == 18
        # the prefix is computed once per family under affinity; round-robin
        # recomputes it once per (family, replica) pair
        assert aff["computed"] < rr["computed"]
        assert aff["reused"] > rr["reused"]
        # ... which is visible as wall-clock warm-TTFT speedup
        assert aff["warm_ttft_mean"] < rr["warm_ttft_mean"], results

    def test_spill_when_affinity_target_is_shedding(self):
        # drive one replica's controller to SHEDDING through real queue
        # depth, then submit a request whose affinity key targets it
        cfg_s = ServingConfig(
            max_queue=4,
            degrade_queue_frac=(0.25, 0.1),
            shed_queue_frac=(0.5, 0.25),
        )
        m, cfg = _model(seed=4)

        def factory(name):
            eng = ContinuousBatchingEngine(
                m, max_slots=2, block_size=4, prompt_bucket=16
            )
            return ServingFrontend(eng, cfg_s)

        cluster = ReplicaCluster(factory, ["r0", "r1", "r2"])
        router = ReplicaRouter(cluster, RouterConfig())
        rng = np.random.default_rng(4)
        probe = router.submit(_prompt(rng, cfg, 8), max_new_tokens=2)
        target = cluster.replicas[probe.replica]
        # back the affinity target up until its controller latches SHEDDING
        fill = []
        while target.frontend.controller.level < 2:
            fill.append(
                target.frontend.submit(_prompt(rng, cfg, 4), max_new_tokens=6)
            )
            target.frontend.pump()
        h = router.submit(
            np.concatenate([probe.prompt[:8], _prompt(rng, cfg, 2)]),
            max_new_tokens=2,
        )
        # same affinity key, but the target is shedding: spilled elsewhere
        assert h.replica != probe.replica
        assert h.routes[0][0] == "spill"
        assert router.routing_counters()["spill"] == 1
        # router pump drives every frontend, so the direct backlog drains too
        _drain_router(router, [probe, h])
        for _ in range(500):
            if all(f.finished for f in fill):
                break
            router.pump()
        assert all(f.finished for f in fill)


# -- death as a routing event --------------------------------------------------

class TestFailover:
    def test_kill_redispatches_and_finishes_with_identical_tokens(self):
        router, cluster, cfg = _cluster(seed=5)
        rng = np.random.default_rng(5)
        prompt = _prompt(rng, cfg, 8)
        # oracle: the same prompt on a healthy cluster
        oracle = router.submit(prompt, max_new_tokens=6)
        _drain_router(router, [oracle])
        victim = router.submit(prompt, max_new_tokens=6)
        router.pump()  # dispatched, some tokens may be out
        owner = victim.replica
        cluster.replicas[owner].kill()
        _drain_router(router, [victim])
        assert victim.outcome == "ok"
        assert victim.redispatches >= 1
        assert victim.redispatches <= router.config.max_redispatch
        # failover is visible in the routes and the replica is DEAD
        assert victim.routes[-1][0] in ("failover", "affinity")
        assert cluster.replicas[owner].state == REPLICA_DEAD
        # deterministic re-generation: the client saw the same stream the
        # healthy cluster would have produced, exactly once
        assert victim.tokens() == oracle.tokens()
        assert len(victim.tokens()) == 6

    def test_salvage_delivers_results_the_dead_engine_already_finished(self):
        router, cluster, cfg = _cluster(seed=6)
        rng = np.random.default_rng(6)
        h = router.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
        replica = cluster.replicas[h.replica]
        # the replica finishes the request entirely on its own pump (the
        # router has not ticked): then it dies before the router ever
        # forwards the result
        for _ in range(50):
            replica.frontend.pump()
            if h.inner.finished:
                break
        assert h.inner.outcome == "ok" and not h.finished
        replica.kill()
        _drain_router(router, [h])
        assert h.outcome == "ok" and len(h.tokens()) == 2
        assert h.redispatches == 0  # delivered, not re-dispatched
        assert router.salvaged_count() == 1

    def test_redispatch_budget_exhaustion_sheds_replica_failure(self):
        router, cluster, cfg = _cluster(
            seed=7, n=2, router_cfg=RouterConfig(max_redispatch=0)
        )
        rng = np.random.default_rng(7)
        h = router.submit(_prompt(rng, cfg, 6), max_new_tokens=8)
        router.pump()
        cluster.replicas[h.replica].kill()
        _drain_router(router, [h])
        # zero budget: the death sheds explicitly, never silently
        assert h.outcome == "replica_failure"
        assert router.shed_counters()["replica_failure"] == 1

    def test_redispatched_request_keeps_original_deadline(self):
        router, cluster, cfg = _cluster(seed=8, n=2)
        rng = np.random.default_rng(8)
        h = router.submit(_prompt(rng, cfg, 6), max_new_tokens=4, ttl_s=3600.0)
        router.pump()
        orig_deadline = h.deadline
        cluster.replicas[h.replica].kill()
        _drain_router(router, [h])
        assert h.outcome == "ok"
        assert h.deadline == orig_deadline  # failover never extends the SLO
        # the replica that finished it saw only the REMAINING budget
        assert h.result(timeout=5.0).deadline <= orig_deadline

    def test_unmakeable_deadline_sheds_at_failover(self):
        router, cluster, cfg = _cluster(
            seed=9, n=2,
            router_cfg=RouterConfig(max_redispatch=3, redispatch_backoff_s=10.0),
        )
        rng = np.random.default_rng(9)
        h = router.submit(_prompt(rng, cfg, 6), max_new_tokens=8, ttl_s=1.0)
        router.pump()
        cluster.replicas[h.replica].kill()
        # the 10s backoff lands past the 1s deadline: deadline-aware shed,
        # no healthy replica's prefill is burned on a request that cannot land
        _drain_router(router, [h])
        assert h.outcome == "deadline_failover"
        assert router.shed_counters()["deadline_failover"] == 1

    def test_revive_rejoins_the_ring_with_fresh_generation(self):
        router, cluster, cfg = _cluster(seed=10)
        rng = np.random.default_rng(10)
        h = router.submit(_prompt(rng, cfg, 6), max_new_tokens=2)
        name = h.replica
        _drain_router(router, [h])
        cluster.replicas[name].kill()
        router.pump()
        assert cluster.replicas[name].state == REPLICA_DEAD
        replica = router.revive(name)
        assert replica.state == REPLICA_UP and replica.generation == 1
        # the revived replica reclaims exactly its old rendezvous share
        h2 = router.submit(h.prompt, max_new_tokens=2)
        assert h2.replica == name
        _drain_router(router, [h2])
        assert h2.outcome == "ok"


# -- drain ---------------------------------------------------------------------

class TestDrain:
    def test_drain_stops_intake_finishes_live_then_resume(self):
        router, cluster, cfg = _cluster(seed=11)
        rng = np.random.default_rng(11)
        obs.GLOBAL_FLIGHT_RECORDER.clear()
        h = router.submit(_prompt(rng, cfg, 8), max_new_tokens=4)
        owner = h.replica
        router.drain(owner)
        assert cluster.replicas[owner].state == REPLICA_DRAINING
        # live work on the draining replica finishes normally — no shed
        _drain_router(router, [h])
        assert h.outcome == "ok" and len(h.tokens()) == 4
        # its ring share remapped: the same key routes elsewhere now
        h2 = router.submit(h.prompt, max_new_tokens=2)
        assert h2.replica != owner
        _drain_router(router, [h2])
        events = [e["kind"] for e in obs.GLOBAL_FLIGHT_RECORDER.snapshot()]
        assert "replica_drained" in events
        router.resume(owner)
        assert cluster.replicas[owner].state == REPLICA_UP
        h3 = router.submit(h.prompt, max_new_tokens=2)
        assert h3.replica == owner  # share reclaimed
        _drain_router(router, [h3])

    def test_all_replicas_draining_rejects_with_no_replicas(self):
        router, cluster, cfg = _cluster(seed=12, n=2)
        rng = np.random.default_rng(12)
        router.drain("r0")
        router.drain("r1")
        with pytest.raises(Overloaded) as ei:
            router.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
        assert ei.value.reason == "no_replicas"
        assert router.shed_counters()["no_replicas"] == 1


# -- health probing + fault sites ----------------------------------------------

class TestHealthAndFaults:
    def test_sites_are_registered_for_campaigns(self):
        assert "router.dispatch" in faults.KNOWN_SITES
        assert "router.health_probe" in faults.KNOWN_SITES
        assert "replica.kill" in faults.KNOWN_SITES
        plan = faults.FaultPlan.sample(faults.KNOWN_SITES, 4, seed=9)
        assert faults.FaultPlan.parse(plan.spec()) == plan

    def test_dispatch_site_fires_before_any_state_change(self):
        router, cluster, cfg = _cluster(seed=13, n=2)
        rng = np.random.default_rng(13)
        with faults.inject(faults.FaultPlan.single("router.dispatch", 0)):
            with pytest.raises(faults.InjectedFault):
                router.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
        assert router.live_requests() == []
        assert sum(router.routing_counters().values()) == 0
        # still open for business
        h = router.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
        _drain_router(router, [h])
        assert h.outcome == "ok"

    def test_health_probe_fault_degrades_then_recovers(self):
        router, cluster, cfg = _cluster(seed=14, n=2)
        rng = np.random.default_rng(14)
        with faults.inject(faults.FaultPlan.single("router.health_probe", 0)):
            router.pump()
        # one failing probe suspects (DEGRADED), never kills — and the
        # replica stays routable throughout
        degraded = [r for r in cluster if r.state == REPLICA_DEGRADED]
        assert len(degraded) == 1 and degraded[0].routable
        router.pump()  # next clean probe restores UP
        assert all(r.state == REPLICA_UP for r in cluster)
        h = router.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
        _drain_router(router, [h])
        assert h.outcome == "ok"

    def test_replica_kill_site_flips_frontend_to_permanent_failure(self):
        router, cluster, cfg = _cluster(seed=15, n=2)
        rng = np.random.default_rng(15)
        handles = [
            router.submit(_prompt(rng, cfg, 6), max_new_tokens=4)
            for _ in range(3)
        ]
        router.pump()
        # call_index 0: the first replica probed on the next pump dies
        with faults.inject(faults.FaultPlan.single("replica.kill", 0)):
            router.pump()
        dead = [r for r in cluster if r.state == REPLICA_DEAD]
        assert len(dead) == 1
        assert dead[0].frontend.engine.broken  # permanent, not transient
        _drain_router(router, handles)
        # death-as-routing-event end to end: every request reached an
        # explicit terminal, none silently lost
        assert all(h.outcome is not None for h in handles)
        assert all(
            h.outcome == "ok" or h.outcome in ("replica_failure",)
            for h in handles
        )


# -- observability -------------------------------------------------------------

class TestClusterObservability:
    def test_replica_state_transitions_are_flight_events(self):
        router, cluster, cfg = _cluster(seed=16, n=2)
        obs.GLOBAL_FLIGHT_RECORDER.clear()
        cluster.replicas["r0"].kill()
        router.pump()
        transitions = [
            e for e in obs.GLOBAL_FLIGHT_RECORDER.snapshot()
            if e["kind"] == "replica_state"
        ]
        assert any(
            e["replica"] == "r0" and e["to"] == REPLICA_DEAD for e in transitions
        )

    def test_all_replicas_dead_dumps_the_black_box(self, tmp_path):
        prior = paddle.get_flags(["FLAGS_flight_recorder_dir"])
        paddle.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
        try:
            router, cluster, cfg = _cluster(seed=17, n=2)
            for r in cluster:
                r.kill()
            router.pump()
            assert all(r.state == REPLICA_DEAD for r in cluster)
            dumps = [
                f for f in os.listdir(tmp_path)
                if "router_all_replicas_dead" in f
            ]
            assert len(dumps) == 1
            payload = json.loads((tmp_path / dumps[0]).read_text())
            kinds = [e["kind"] for e in payload["events"]]
            assert "all_replicas_dead" in kinds
        finally:
            paddle.set_flags(prior)

    def test_failover_span_shows_both_replicas_in_one_trace(self):
        prior = paddle.get_flags(["FLAGS_trace_sample_rate", "FLAGS_trace_seed"])
        paddle.set_flags(
            {"FLAGS_trace_sample_rate": 1.0, "FLAGS_trace_seed": 77}
        )
        obs.GLOBAL_TRACER.clear()
        try:
            router, cluster, cfg = _cluster(seed=18, n=2)
            rng = np.random.default_rng(18)
            h = router.submit(_prompt(rng, cfg, 6), max_new_tokens=4)
            router.pump()
            first_owner = h.replica
            cluster.replicas[first_owner].kill()
            _drain_router(router, [h])
            assert h.outcome == "ok" and h.replica != first_owner
            spans = obs.GLOBAL_TRACER.spans(trace_id=h.trace_ctx.trace_id)
            names = [s["name"] for s in spans]
            # both replicas' request trees + the failover bridge + the root,
            # all in ONE trace
            assert names.count("request") == 2
            assert "router.failover" in names
            assert "router.request" in names
            failover = next(s for s in spans if s["name"] == "router.failover")
            assert failover["attrs"]["from_replica"] == first_owner
            assert failover["attrs"]["to_replica"] == h.replica
            # the failover span and the request spans parent into the root
            root = next(s for s in spans if s["name"] == "router.request")
            assert failover["parent_id"] == root["span_id"]
            assert root["attrs"]["redispatches"] == h.redispatches
        finally:
            obs.GLOBAL_TRACER.clear()
            paddle.set_flags(prior)


# -- the seeded churn property test -------------------------------------------

class TestRouterChurnProperty:
    def test_churn_over_submit_kill_revive_drain_pump(self):
        """ISSUE satellite: N ops over submit/kill/revive/drain/pump —
        after EVERY op: each live request is owned by exactly one replica
        (and that replica's frontend agrees), terminal-exactly-once across
        failovers, re-dispatch count <= budget, and the routing counters
        account every routing decision exactly."""
        router, cluster, cfg = _cluster(
            seed=19, max_queue=6,
            router_cfg=RouterConfig(max_redispatch=2, redispatch_backoff_s=0.001),
        )
        rng = np.random.default_rng(19)
        families = [_prompt(rng, cfg, 8) for _ in range(3)]
        accepted = {}
        terminal = {}
        rejected = 0

        def note_done(handles):
            for h in handles:
                assert h.id not in terminal, "delivered twice"
                terminal[h.id] = h.outcome

        def check_invariants():
            # counters reconcile with the monotonic dispatch count after
            # every op (and with the log, which retains everything at this
            # scale)
            counters = router.routing_counters()
            assert sum(counters.values()) == router.dispatch_count()
            assert router.dispatch_count() == len(router.routing_log())
            live = router.live_requests()
            for rr in live:
                assert not rr.finished
                # owned by exactly one replica (or None only while no
                # routable failover target exists)
                if rr.replica is not None:
                    assert rr.replica in cluster.replicas
                assert rr.redispatches <= router.config.max_redispatch
                if rr.inner is not None:
                    # exactly the owner's frontend holds this inner handle
                    # (identity check: inner ids are per-engine counters and
                    # may collide numerically across replicas)
                    holders = [
                        r.name for r in cluster
                        if r.frontend._live.get(rr.inner.id) is rr.inner
                    ]
                    assert holders in ([rr.replica], []), (holders, rr.replica)
            # every terminal is explicit
            assert all(out is not None for out in terminal.values())

        for step in range(140):
            op = rng.random()
            if op < 0.45:
                fam = families[int(rng.integers(0, 3))]
                prompt = np.concatenate([fam, _prompt(rng, cfg, int(rng.integers(1, 4)))])
                ttl = None if rng.random() < 0.7 else float(rng.choice([1e-5, 3600.0]))
                try:
                    h = router.submit(
                        prompt,
                        max_new_tokens=int(rng.integers(2, 6)),
                        priority=int(rng.integers(0, 3)),
                        tenant=str(rng.choice(["a", "b"])),
                        ttl_s=ttl,
                    )
                    accepted[h.id] = h
                except Overloaded:
                    rejected += 1
            elif op < 0.75:
                note_done(router.pump())
            elif op < 0.83:
                alive = [r for r in cluster if r.alive]
                if len(alive) >= 2:
                    victim = alive[int(rng.integers(0, len(alive)))]
                    victim.kill()
            elif op < 0.90:
                dead = [r for r in cluster if r.state == REPLICA_DEAD]
                if dead:
                    router.revive(dead[int(rng.integers(0, len(dead)))].name)
            elif op < 0.95:
                routable = [r for r in cluster if r.routable]
                if len(routable) >= 2:
                    router.drain(routable[int(rng.integers(0, len(routable)))].name)
            else:
                draining = [r for r in cluster if r.state == REPLICA_DRAINING]
                if draining:
                    router.resume(draining[0].name)
            check_invariants()

        # park the cluster healthy and drain everything to terminal
        for r in cluster:
            if r.state == REPLICA_DEAD:
                router.revive(r.name)
        for r in cluster:
            if r.state == REPLICA_DRAINING:
                router.resume(r.name)
        for _ in range(1000):
            note_done(router.pump())
            check_invariants()
            if all(h.finished for h in accepted.values()):
                break
        # terminal-exactly-once, cluster-wide, nobody lost
        assert set(terminal) == set(accepted)
        outcomes = set(terminal.values())
        assert "ok" in outcomes
        # churn deep enough to exercise the failover path
        assert any(h.redispatches > 0 for h in accepted.values()) or (
            "replica_failure" in outcomes
        )
        # router sheds reconcile with router-originated terminals
        router_shed_outcomes = ("replica_failure", "deadline_failover")
        sheds = router.shed_counters()
        for reason in router_shed_outcomes:
            assert sheds.get(reason, 0) == sum(
                1 for o in terminal.values() if o == reason
            )


# -- the kill-mid-storm acceptance test ---------------------------------------

class TestKillMidStormAcceptance:
    def test_kill_mid_storm_loses_zero_requests_silently(self):
        """ISSUE acceptance: 3 replicas under calibrated 2x overload, one
        replica killed mid-storm via the fault site. Every in-flight
        request on the dead replica is either delivered (salvaged /
        re-dispatched and finished) or shed with an explicit terminal;
        terminal-exactly-once holds cluster-wide; the recompile watchdog
        still reports exactly 1 compiled signature per surviving engine."""
        obs.GLOBAL_WATCHDOG.reset()
        router, cluster, cfg = _cluster(seed=20, max_queue=6)
        # calibrate on one replica, warm the rest so the storm adds nothing
        rate = measure_sustainable_rate(
            cluster.replicas["r0"].frontend, 6, seed=20,
            prompt_len=(3, 7), max_new_tokens=(3, 8),
            vocab_size=cfg.vocab_size,
        )
        rng = np.random.default_rng(20)
        for name in ("r1", "r2"):
            fe = cluster.replicas[name].frontend
            h = fe.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
            while not h.finished:
                fe.pump()
        mix = [
            TrafficClass("chat", Priority.INTERACTIVE, 1.0, (3, 7), (3, 8), 2.0),
            TrafficClass("batch", Priority.BEST_EFFORT, 1.0, (3, 7), (3, 8), 2.0),
        ]
        arrivals = poisson_arrivals(
            2.0 * 3 * rate, 36, mix, seed=21, vocab_size=cfg.vocab_size
        )
        kill_at = arrivals[len(arrivals) // 3].t
        state = {"killed": False}

        def mid_storm(router_, now):
            if not state["killed"] and now >= kill_at:
                state["killed"] = True
                faults.install_plan(faults.FaultPlan.single("replica.kill", 0))

        try:
            report = run_cluster_open_loop(
                router, arrivals, max_wall_s=90.0, on_iteration=mid_storm
            )
        finally:
            faults.install_plan(None)
        assert state["killed"]
        assert report["undelivered_arrivals"] == 0, report
        dead = [r for r in cluster if r.state == REPLICA_DEAD]
        assert len(dead) == 1  # the kill landed, exactly one replica died
        # ZERO silent losses: everything accepted reached exactly one
        # explicit terminal (accepted == in-SLO + late + explicit sheds)
        for key, pc in report["per_class"].items():
            assert (
                pc["accepted"]
                == pc["finished_in_slo"] + pc["finished_late"] + pc["shed_after_accept"]
            ), (key, pc)
        # the death was handled as a routing event: salvage or failover ran
        assert report["failovers"] + report["salvaged"] >= 1, report
        # router-originated sheds are explicit terminals, never silence
        for reason in report["router_sheds"]:
            assert reason in ("replica_failure", "deadline_failover", "no_replicas")
        # counters account every routing decision exactly
        assert sum(report["routes"].values()) == report["dispatches"]
        # 1 compiled signature per engine (3 built), zero added by the storm
        assert report["compiled_signatures_total"] == 3, report
        assert sum(report["compiles_during_run"].values()) == 0, report


# -- multi-replica HTTP mode ---------------------------------------------------

class TestClusterHTTP:
    def test_router_behind_the_http_endpoint(self):
        router, cluster, cfg = _cluster(seed=21, n=2)
        srv = start_serving_server(router, port=0)
        port = srv.server_address[1]
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request(
                "POST", "/v1/generate",
                json.dumps({"prompt": [1, 2, 3, 4], "max_new_tokens": 3}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            body = resp.read().decode()
            conn.close()
            assert resp.status == 200
            lines = [json.loads(l) for l in body.strip().splitlines()]
            assert lines[-1]["done"] is True and lines[-1]["outcome"] == "ok"
            assert lines[-1]["tokens"] == 3
            # /healthz is the cluster view: per-replica states + counters
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.request("GET", "/healthz")
            snap = json.loads(conn.getresponse().read().decode())
            conn.close()
            assert set(snap["replicas"]) == {"r0", "r1"}
            assert snap["routable_replicas"] == 2
            assert sum(snap["routes"].values()) >= 1
        finally:
            stop_serving_server(router)
