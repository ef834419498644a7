"""Prefix-affinity replica fleet (PR 14).

Covers the :class:`~llm_consensus_tpu.serving.fleet.ReplicaSet` /
:class:`PrefixRouter` subsystem end to end: affinity routing lands a
panel's mates on the donor's replica (the shared header prefills once
FLEET-wide), the preempt→demote→re-admit round trip is byte-identical,
rebalancing exports a chain through the fleet-shared
:class:`HostPageStore` and the next hit restores it on another replica,
the shared store stays correct under concurrent demotes from two
replicas (the PR-14 lock audit), scoped keys keep heterogeneous
replicas from cross-restoring, the gateway's ``/readyz`` aggregates
per-replica heartbeats (one wedged replica flips readiness, reported by
index, and the router stops routing to it), metrics/stats move in
lockstep, the affinity policy's registry hit rate sits above the
round-robin control's on the same burst with the same text, and an
overload storm through one gateway resolves by preemption: no 429, at
least one preempt and one restored page.
"""

import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import (
    PagePool,
    PrefixRegistry,
    prefix_chain_key,
)
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.server.metrics import REGISTRY
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.serving.fleet import (
    FleetBackend,
    FleetConfig,
    ReplicaSet,
)
from llm_consensus_tpu.serving.offload import HostPageStore

CFG = get_config("test-tiny")

# 49 chars -> 3 full 16-token pages + a tail at page_size 16.
_HEADER = "Panel shared header for every persona, forty ch: "

# Small enough to stay fast, big enough for 2 replicas to serve
# concurrently; the preempt test overrides n_pages to starve the pool.
_FCFG = dict(
    max_slots=2,
    page_size=16,
    n_pages=32,
    pages_per_seq=8,
    max_new_tokens=4,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
    host_cache_bytes=64 << 20,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _serve(target, prompts, **kw):
    futs = [target.submit(p, **kw) for p in prompts]
    return [f.result(timeout=300).text for f in futs]


def _fleet(params, replicas=2, fleet_kw=None, **cfg_over):
    return ReplicaSet(
        CFG,
        params,
        config=ContinuousConfig(**{**_FCFG, **cfg_over}),
        fleet=FleetConfig(replicas=replicas, **(fleet_kw or {})),
    )


# ---------------------------------------------------------------------------
# Chain fingerprint + read-only probe (the router's primitives)
# ---------------------------------------------------------------------------


def test_prefix_chain_key_matches_registry_identity():
    ids = list(range(100, 135))  # 35 tokens, page 16
    chain = prefix_chain_key(ids, 16)
    # (35 - 1) // 16 = 2 usable full pages — the last token always
    # recomputes, exactly the registry's match cap.
    assert len(chain) == 2
    assert chain[0] == tuple(range(100, 116))
    assert chain[1] == tuple(range(116, 132))
    # 33 tokens: the 2nd page's last token would be the prompt's last.
    assert len(prefix_chain_key(ids[:33], 16)) == 2
    assert len(prefix_chain_key(ids[:32], 16)) == 1
    assert prefix_chain_key(ids[:16], 16) == ()


def test_registry_probe_is_read_only():
    pool = PagePool(range(1, 16))
    reg = PrefixRegistry(pool, 4)
    ids = list(range(50, 62))  # 2 usable full pages + tail
    pages = pool.alloc(2)
    created = reg.register(ids, pages)
    rc_before = {p: pool.refcount(p) for p in pages}
    lookups, hits = reg.lookups, reg.hits
    nodes, tokens = reg.probe(ids)
    assert tokens == 8 and len(nodes) == 2
    # NO side effects: refcounts, counters, and LRU ticks untouched.
    assert {p: pool.refcount(p) for p in pages} == rc_before
    assert (reg.lookups, reg.hits) == (lookups, hits)
    # Unready nodes count (burst mates probe an in-flight prefill).
    assert not created[0][0].ready
    # A diverging prompt stops at the divergence page.
    other = ids[:4] + [999] * 8
    _, t2 = reg.probe(other)
    assert t2 == 4


# ---------------------------------------------------------------------------
# Shared store: concurrency (the PR-14 lock audit) + scoped keys
# ---------------------------------------------------------------------------


def test_store_touch_reports_lost_race():
    store = HostPageStore(budget_bytes=1 << 20)
    planes = (np.ones((4, 8), np.float32),)
    assert store.put(("a",), planes)
    assert store.touch(("a",)) is True
    assert store.touch(("gone",)) is False  # caller must re-fetch+put


def test_store_concurrent_demote_accounting_stays_exact():
    """Two 'replicas' demote overlapping chain sets concurrently; the
    byte accounting, LRU order, and per-call deltas must stay exact
    under interleaving (put_counted returns THIS call's deltas — the
    caller never reconstructs them from global counters)."""
    page = 128  # 4*8 float32
    store = HostPageStore(budget_bytes=64 * page)
    demoted = [0, 0]
    dropped = [0, 0]
    errs = []

    def replica(idx):
        try:
            rng = np.random.default_rng(idx)
            for round_ in range(40):
                for c in range(32):
                    key = ("chain", c % 24)  # overlapping key space
                    if not store.touch(key):
                        planes = (
                            rng.standard_normal((4, 8)).astype(np.float32),
                        )
                        _, d, dr = store.put_counted(key, planes)
                        demoted[idx] += d
                        dropped[idx] += dr
                    else:
                        demoted[idx] += 1
                    store.get(("chain", (c * 7) % 24))
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=replica, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    # Exact invariants after arbitrary interleaving: resident bytes
    # match the entries, per-call deltas sum to the global counters.
    assert store.bytes_used == len(store) * page
    assert store.bytes_used <= store.budget_bytes
    assert store.demoted_pages == demoted[0] + demoted[1]
    assert store.dropped_pages == dropped[0] + dropped[1]
    assert len(store) <= 24


def test_store_scope_blocks_heterogeneous_cross_restore(params):
    """Two batchers with DIFFERENT weights share one store: the second
    must never restore the first's pages (a page's bytes are a
    function of the weights that wrote it) — store keys carry the
    config+weights scope, so B's probe misses A's entries."""
    params_b = init_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)
    store = HostPageStore(budget_bytes=64 << 20)
    prompts = [_HEADER + f"q{i}" for i in range(2)]
    a = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_FCFG), host_store=store
    )
    try:
        _serve(a, prompts)
        a.request_preempt(8)
        deadline = time.time() + 30
        while a.stats()["preempted_pages"] == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert a.stats()["preempted_pages"] > 0
        assert len(store) > 0
    finally:
        a.close()
    b = ContinuousBatcher(
        CFG, params_b, config=ContinuousConfig(**_FCFG), host_store=store
    )
    try:
        before = len(store)
        out = _serve(b, prompts)  # same chains, different weights
        s_b = b.stats()
    finally:
        b.close()
    assert s_b["offload_restored_pages"] == 0  # scope mismatch = miss
    assert len(out) == 2 and all(isinstance(t, str) for t in out)
    assert len(store) == before  # B re-prefilled; nothing was consumed


# ---------------------------------------------------------------------------
# Affinity routing: the panel lands on its donor's replica
# ---------------------------------------------------------------------------


def test_affinity_burst_lands_on_donor_replica(params):
    fleet = _fleet(params)
    try:
        texts = _serve(fleet, [_HEADER + f"q{i}" for i in range(4)])
        s = fleet.stats()
        # Every mate routed to ONE replica (1 load/first + 3 prefix).
        assert s["routed_prefix"] == 3
        per_req = [sum(r.values()) for r in s["routed"]]
        assert sorted(per_req) == [0, 4]
        donor = per_req.index(4)
        per = s["per_replica"]
        assert per[donor]["completed_requests"] == 4
        assert per[1 - donor]["completed_requests"] == 0
        # The shared header prefilled ONCE fleet-wide: the other
        # replica ran no prefill chunks at all, and the donor's
        # registry served the mates' pages.
        assert per[1 - donor]["prefill_chunks"] == 0
        assert per[donor]["prefix_pages_shared"] >= 9  # 3 pages x 3 mates
        assert s["prefix_hit_rate"] == pytest.approx(0.75)
        # Unique traffic still spreads across replicas by modeled load.
        _serve(fleet, [f"{i} unique prompt with its own padding {i}" for i in range(4)])
        s2 = fleet.stats()
        assert all(
            p["completed_requests"] > 0 for p in s2["per_replica"]
        )
        assert len(texts) == 4
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Preempt -> demote -> re-admit: byte-identical, nothing lost
# ---------------------------------------------------------------------------


def test_preempt_demote_readmit_round_trip_byte_identical(params):
    """The overload contract: a router-requested preemption demotes
    the panel's resident chains to the shared tier; re-sending the
    same panel then RESTORES them — and the round-tripped texts are
    byte-identical to the originals (the demote/restore path is
    bit-exact, PR 4, now router-triggered)."""
    fleet = _fleet(params)
    try:
        prompts = [_HEADER + f"q{i}" for i in range(3)]
        want = _serve(fleet, prompts)
        # Queue-full moment: the hook preempts (tier has headroom).
        assert fleet.preempt_for_admission() is True
        deadline = time.time() + 30
        while (
            fleet.stats()["preempted_pages"] == 0
            and time.time() < deadline
        ):
            time.sleep(0.02)
        s1 = fleet.stats()
        assert s1["preempted_pages"] > 0
        assert sum(s1["preempt_requests"]) == 1
        assert s1["shared_store_pages"] > 0
        # Re-admit the same panel: the chains come back from the tier.
        got = _serve(fleet, prompts)
        s2 = fleet.stats()
    finally:
        fleet.close()
    assert got == want
    assert s2["offload_restored_pages"] > 0


def test_preempt_hook_sheds_only_when_tier_exhausted(params):
    """The hook's False conditions: no tier at all, a tier too full to
    absorb one more page without evicting preserved work, or traffic
    that registers NOTHING shareable (an unbounded queue with nothing
    to ever preempt must keep its classic 429 backpressure)."""
    no_tier = _fleet(params, host_cache_bytes=0)
    try:
        assert no_tier.store is None
        assert no_tier.preempt_for_admission() is False
    finally:
        no_tier.close()
    fleet = _fleet(params)
    try:
        # Nothing registered yet: nothing to preserve => shed.
        assert fleet.preempt_for_admission() is False
        _serve(fleet, [_HEADER + "q0"])
        # Resident chains + tier headroom: preempt instead of shed.
        assert fleet.preempt_for_admission() is True
        # Shrink the headroom below one page: exhaustion => shed.
        fleet.store.budget_bytes = 1
        assert fleet.preempt_for_admission() is False
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Rebalance: move the chain through the shared store
# ---------------------------------------------------------------------------


def test_rebalance_chain_next_hit_restores_remotely(params):
    fleet = _fleet(params)
    try:
        prompts = [_HEADER + f"q{i}" for i in range(2)]
        want = _serve(fleet, prompts)
        s0 = fleet.stats()
        donor = max(
            range(2),
            key=lambda i: s0["per_replica"][i]["completed_requests"],
        )
        owner = fleet.rebalance_chain(_HEADER + "q0")
        assert owner == donor
        s1 = fleet.stats()
        assert s1["exported_pages"] >= 3  # the header's full pages
        assert s1["shared_store_pages"] >= 3
        # The chain is now restorable ANYWHERE: the other replica's
        # admission host-hits and restores it remotely.
        other = 1 - donor
        got = fleet.submit_to(
            other, _HEADER + "q0", max_new_tokens=4
        ).result(timeout=300)
        s2 = fleet.stats()
        assert got.text == want[0]
        assert s2["per_replica"][other]["offload_restored_pages"] >= 3
    finally:
        fleet.close()


def test_router_rebalances_away_from_congested_owner(params):
    """Auto-rebalance: the affinity owner's batcher queue is deeper
    than the configured bound, so the router exports the chain and
    re-homes the mate to the idle replica — correctness unchanged."""
    fleet = _fleet(params, fleet_kw={"rebalance_waiting": 0})
    try:
        donor_text = _serve(fleet, [_HEADER + "q0"])[0]
        s0 = fleet.stats()
        donor = max(
            range(2),
            key=lambda i: s0["per_replica"][i]["completed_requests"],
        )
        # Congest the donor: more work than its slots, so its batcher
        # queue is non-empty when the mate routes.
        blockers = [
            fleet.submit_to(
                donor, f"{i} blocker with plenty of padding text {i}",
                max_new_tokens=4,
            )
            for i in range(5)
        ]
        fut = fleet.submit(_HEADER + "q0", max_new_tokens=4)
        out = fut.result(timeout=300)
        for b in blockers:
            b.result(timeout=300)
        s = fleet.stats()
    finally:
        fleet.close()
    rebalanced = sum(r["rebalance"] for r in s["routed"])
    assert rebalanced == 1
    assert s["routed"][1 - donor]["rebalance"] == 1
    assert out.text == donor_text  # routing never changes output


# ---------------------------------------------------------------------------
# /readyz aggregation + router health
# ---------------------------------------------------------------------------


def test_wedged_replica_flips_readyz_by_index_and_router_skips(params):
    from llm_consensus_tpu.server.client import (
        GatewayClient,
        GatewayHTTPError,
    )
    from llm_consensus_tpu.server.gateway import (
        Gateway,
        GatewayConfig,
        GatewayThread,
    )
    from llm_consensus_tpu.server.metrics import MetricsRegistry

    fleet = _fleet(params, fleet_kw={"ready_stall_s": 1.0})
    handle = GatewayThread(
        Gateway(
            FleetBackend(fleet),
            config=GatewayConfig(port=0, ready_stall_s=1.0),
            registry=MetricsRegistry(),
        )
    ).start()
    client = GatewayClient("127.0.0.1", handle.port)

    def poll(want_ready, deadline_s=20.0):
        deadline = time.time() + deadline_s
        last = None
        while time.time() < deadline:
            try:
                doc = client.readyz()
                if doc["ready"] is want_ready:
                    return doc
                last = doc
            except GatewayHTTPError as e:
                if not want_ready and e.status == 503:
                    return json.loads(e.body)
                last = e.body
            time.sleep(0.1)
        raise AssertionError(f"readyz never reached {want_ready}: {last}")

    try:
        doc = poll(True)
        hb = doc["backend"]
        assert len(hb["replicas"]) == 2  # per-replica heartbeats ride
        assert hb["alive"] is True
        # Wedge replica 1's loop (instance attribute shadows the bound
        # method — the same trick the PR-5 readyz test uses).
        fleet.batchers[1]._admit = lambda: time.sleep(3.0)
        try:
            doc = poll(False)
            assert doc["wedged_replicas"] == [1]
            # The router stops routing to the wedged replica...
            assert fleet.router.healthy() == [0]
            # ...and live traffic still completes on the healthy one.
            before = fleet.batchers[0].stats()["completed_requests"]
            out = fleet.submit(
                "traffic while replica 1 is wedged", max_new_tokens=4
            ).result(timeout=300)
            assert isinstance(out.text, str)
            assert (
                fleet.batchers[0].stats()["completed_requests"]
                == before + 1
            )
        finally:
            del fleet.batchers[1]._admit
        poll(True)  # recovery
    finally:
        handle.drain()
        fleet.close()


# ---------------------------------------------------------------------------
# Metrics <-> stats lockstep
# ---------------------------------------------------------------------------


def test_replica_metrics_stats_lockstep(params):
    def routed_snapshot():
        out = {}
        for key, v in REGISTRY.snapshot().items():
            m = re.match(
                r'gateway_replica_routed_total\{reason="(\w+)",'
                r'replica="(\d+)"\}',
                key,
            )
            if m:
                out[(int(m.group(2)), m.group(1))] = v
        return out

    r0 = routed_snapshot()
    pre0 = {
        i: REGISTRY.get("gateway_replica_preemptions_total")
        .labels(replica=str(i))
        .value
        for i in (0, 1)
    }
    fleet = _fleet(params)
    try:
        _serve(fleet, [_HEADER + f"q{i}" for i in range(3)])
        assert fleet.preempt_for_admission() is True
        s = fleet.stats()
        r1 = routed_snapshot()
        # Routed counters move exactly with the stats() mirror.
        for i, reasons in enumerate(s["routed"]):
            for reason, n in reasons.items():
                if n:
                    assert r1.get((i, reason), 0) - r0.get((i, reason), 0) == n
        # Preemption counter mirrors preempt_requests per replica.
        for i in (0, 1):
            delta = (
                REGISTRY.get("gateway_replica_preemptions_total")
                .labels(replica=str(i))
                .value
                - pre0[i]
            )
            assert delta == s["preempt_requests"][i]
        # Gauges refreshed by the stats pull match the per-replica
        # stats they were computed from.
        for i, per in enumerate(s["per_replica"]):
            programs = sum(
                per[f"device_programs_{k}"]
                for k in ("fused", "decode", "prefill", "spec", "draft")
            )
            g = REGISTRY.get("gateway_replica_programs").labels(
                replica=str(i)
            )
            assert g.value == programs
            hr = REGISTRY.get("gateway_replica_prefix_hit_rate").labels(
                replica=str(i)
            )
            assert hr.value == pytest.approx(
                per["prefix_hits"] / max(1, per["prefix_lookups"])
            )
        assert REGISTRY.get(
            "gateway_replica_shared_store_bytes"
        ).value == s["shared_store_bytes"]
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Affinity against the round-robin control; the overload storm
# ---------------------------------------------------------------------------


def test_affinity_hit_rate_above_round_robin_control_same_text(params):
    """The same mixed burst (a four-mate panel first, then unique
    prompts) through a fleet routing by ``"prefix"`` and one routing
    ``"random"`` (round-robin: it scatters the mates, so each replica
    prefills the header for itself): the affinity fleet's registry hit
    rate is strictly the higher, and routing changes no text."""
    prompts = [_HEADER + f"q{i}" for i in range(4)] + [
        f"{i} unique prompt with its own padding {i}" for i in range(2)
    ]

    def run(policy):
        fleet = _fleet(params, fleet_kw={"policy": policy})
        try:
            return _serve(fleet, prompts), fleet.stats()
        finally:
            fleet.close()

    texts_aff, s_aff = run("prefix")
    texts_rr, s_rr = run("random")
    assert texts_aff == texts_rr
    assert s_aff["routed_prefix"] == 3 and s_rr["routed_prefix"] == 0
    assert s_aff["prefix_hit_rate"] > s_rr["prefix_hit_rate"]


def test_overload_storm_resolves_by_preemption_not_429s(params):
    """Working-set-starved pools behind one gateway whose queue bound
    sits far below the storm: a wave primes header A, a storm of
    header B overflows the queue on most submits — the fleet's overflow
    hook preempts resident chains to the shared host tier instead of
    shedding — and the re-vote wave of header A restores from the tier.
    Nothing is shed, nothing is lost, >= 1 preempt, >= 1 restored
    page."""
    from llm_consensus_tpu.server.admission import AdmissionConfig
    from llm_consensus_tpu.server.client import GatewayClient
    from llm_consensus_tpu.server.gateway import (
        Gateway,
        GatewayConfig,
        GatewayThread,
    )

    def shed_total():
        return sum(
            v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("gateway_shed_total")
        )

    # 16 usable pages a replica against 5 a sequence: chains cannot
    # stay on the device across waves.
    fleet = _fleet(params, n_pages=17)
    handle = GatewayThread(
        Gateway(
            FleetBackend(fleet),
            config=GatewayConfig(
                port=0,
                admission=AdmissionConfig(max_queue=2, max_inflight=2),
            ),
        )
    ).start()
    other = "Storm shared header for the second panel, fort: "  # 49 chars
    waves = [
        [_HEADER + f"p{i}" for i in range(2)],
        [other + f"s{i}" for i in range(10)],
        [_HEADER + f"r{i}" for i in range(2)],
    ]
    errors: list[str] = []
    done: list[int] = []

    def call(client, prompt):
        try:
            r = client.generate(prompt, max_new_tokens=4, temperature=0.0)
            done.append(r["num_tokens"])
        except Exception as e:  # noqa: BLE001 - counted below, not raised
            errors.append(repr(e))

    shed0 = shed_total()
    try:
        client = GatewayClient("127.0.0.1", handle.port, timeout=300.0)
        for wave in waves:
            threads = [
                threading.Thread(target=call, args=(client, p)) for p in wave
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads)
        s = fleet.stats()
    finally:
        handle.drain()
        fleet.close()
    assert errors == []
    assert len(done) == sum(len(w) for w in waves) and all(done)
    assert shed_total() == shed0
    assert sum(s["preempt_requests"]) >= 1
    assert s["offload_restored_pages"] >= 1
