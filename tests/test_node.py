"""Node runtime tests (reference node/core_test.go, node/node_test.go).

- scripted Core playbook: deterministic gossip sequence through diff/sync,
  asserting identical consensus across cores (TestConsensus pattern);
- live gossip over the in-memory network until every node commits the
  submitted transactions, asserting prefix agreement (TestGossip pattern);
- stats schema.
"""

import asyncio
from dataclasses import dataclass
from typing import List

import pytest

from babble_tpu.crypto.keys import generate_key
from babble_tpu.net import InmemNetwork, Peer
from babble_tpu.node import Config, Core, Node
from babble_tpu.node.peer_selector import RandomPeerSelector
from babble_tpu.proxy.inmem import InmemAppProxy


def _make_cores(n=3):
    keys = sorted([generate_key() for _ in range(n)], key=lambda k: k.pub_hex)
    participants = {k.pub_hex: i for i, k in enumerate(keys)}
    cores = [
        Core(i, keys[i], participants, e_cap=256) for i in range(n)
    ]
    for c in cores:
        c.init()
    return cores


def _synchronize(from_core: Core, to_core: Core, payload: List[bytes]):
    """In-process gossip: `to` pulls from `from` (core_test.go:389-402)."""
    known = to_core.known()
    diff = from_core.diff(known)
    wire = from_core.to_wire(diff)
    to_core.sync(from_core.head, wire, payload)


@dataclass
class Play:
    frm: int
    to: int
    payload: List[bytes]


def test_core_scripted_consensus():
    # Fame needs voting rounds ≥2 past a witness's round, so the script must
    # span several rounds before any event reaches consensus order
    # (reference core_test.go:339-387 uses a similar multi-round playbook).
    cores = _make_cores(3)
    pattern = [(0, 1), (1, 0), (2, 1), (1, 2), (0, 2), (2, 0)]
    plays = [
        Play(*pattern[i % len(pattern)], [f"tx{i}".encode()])
        for i in range(40)
    ]
    for p in plays:
        _synchronize(cores[p.frm], cores[p.to], p.payload)

    for c in cores:
        c.run_consensus()

    # all cores that have the full picture agree on the consensus prefix
    base = cores[1].hg.consensus_events()
    assert len(base) > 0
    for c in cores:
        got = c.hg.consensus_events()
        k = min(len(got), len(base))
        assert got[:k] == base[:k], f"core {c.id} disagrees"


def test_core_diff_is_minimal():
    cores = _make_cores(2)
    _synchronize(cores[0], cores[1], [b"x"])
    # core1 now has 3 events (2 roots + its new head), core0 has 1
    known0 = cores[0].known()
    diff = cores[1].diff(known0)
    hexes = {e.hex() for e in diff}
    assert cores[1].head in hexes
    assert len(diff) == 2  # core1's root + new head; core0 has its own root
    _synchronize(cores[1], cores[0], [])
    # core0 pulled everything core1 had, then minted a new head of its own —
    # so it knows at least as much as core1 on every axis and strictly more
    # about itself.
    k0, k1 = cores[0].known(), cores[1].known()
    assert all(k0[i] >= k1[i] for i in k1)
    assert k0[0] > k1[0]


def _run_gossip_network(n_nodes, n_txs, timeout=45.0):
    async def go():
        net = InmemNetwork()
        keys = sorted(
            [generate_key() for _ in range(n_nodes)], key=lambda k: k.pub_hex
        )
        transports = [net.transport() for _ in range(n_nodes)]
        peers = [
            Peer(net_addr=t.local_addr(), pub_key_hex=k.pub_hex)
            for t, k in zip(transports, keys)
        ]
        proxies = [InmemAppProxy() for _ in range(n_nodes)]
        nodes = [
            Node(Config.test_config(heartbeat=0.01), keys[i], peers,
                 transports[i], proxies[i])
            for i in range(n_nodes)
        ]
        for nd in nodes:
            nd.init()
            nd.run_task(gossip=True)

        for i in range(n_txs):
            await proxies[i % n_nodes].submit_tx(f"tx{i}".encode())

        async def all_committed():
            while True:
                if all(
                    len(p.committed_transactions()) >= n_txs for p in proxies
                ):
                    return
                await asyncio.sleep(0.05)

        try:
            await asyncio.wait_for(all_committed(), timeout)
        finally:
            for nd in nodes:
                await nd.shutdown()
        return nodes, proxies

    return asyncio.run(go())


@pytest.mark.slow
def test_gossip_agreement():
    n_txs = 6
    nodes, proxies = _run_gossip_network(3, n_txs)

    # every node delivered all submitted txs, in the same order
    base = proxies[0].committed_transactions()
    txs = {f"tx{i}".encode() for i in range(n_txs)}
    assert txs.issubset(set(base))
    for p in proxies[1:]:
        got = p.committed_transactions()
        k = min(len(got), len(base))
        assert got[:k] == base[:k]

    # consensus event lists agree too
    lists = [nd.core.hg.consensus_events() for nd in nodes]
    k = min(len(l) for l in lists)
    assert k > 0
    for l in lists[1:]:
        assert l[:k] == lists[0][:k]


def test_stats_schema():
    async def go():
        net = InmemNetwork()
        key = generate_key()
        t = net.transport()
        peers = [Peer(net_addr=t.local_addr(), pub_key_hex=key.pub_hex)]
        node = Node(Config.test_config(), key, peers, t, InmemAppProxy())
        node.init()
        stats = node.get_stats()
        for k in (
            "last_consensus_round", "consensus_events",
            "consensus_transactions", "undetermined_events",
            "transaction_pool", "num_peers", "sync_rate",
            "events_per_second", "rounds_per_second", "round_events", "id",
        ):
            assert k in stats, k
        assert stats["sync_rate"] == "1.00"
        await node.shutdown()

    asyncio.run(go())


def test_random_peer_selector_excludes_self_and_last():
    peers = [
        Peer(net_addr=f"a{i}", pub_key_hex=f"0x{i}") for i in range(3)
    ]
    sel = RandomPeerSelector(peers, "a0")
    picks = {sel.next().net_addr for _ in range(50)}
    assert "a0" not in picks
    sel.update_last("a1")
    picks = {sel.next().net_addr for _ in range(50)}
    assert picks == {"a2"}


def test_random_peer_selector_default_stream_is_identity_seeded():
    """Regression for a consensus-nondeterminism finding (ISSUE 4): the
    default RNG was OS-entropy seeded, making peer choice — which
    shapes the DAG — the one per-node decision unreproducible from
    identity + seed.  Two selectors with the same identity must draw
    the same stream; an explicit rng still overrides."""
    import random

    peers = [
        Peer(net_addr=f"a{i}", pub_key_hex=f"0x{i}") for i in range(5)
    ]
    a = RandomPeerSelector(peers, "a0")
    b = RandomPeerSelector(peers, "a0")
    assert ([a.next().net_addr for _ in range(30)]
            == [b.next().net_addr for _ in range(30)])
    # different identity -> different (but still deterministic) stream
    c1 = RandomPeerSelector(peers, "a1")
    c2 = RandomPeerSelector(peers, "a1")
    assert ([c1.next().net_addr for _ in range(30)]
            == [c2.next().net_addr for _ in range(30)])
    # explicit rng wins (the chaos runner's shared-seed control path)
    d = RandomPeerSelector(peers, "a0", rng=random.Random(7))
    e = RandomPeerSelector(peers, "a0", rng=random.Random(7))
    assert d.next().net_addr == e.next().net_addr


def test_heartbeat_pacing_is_identity_seeded():
    """Regression for the second consensus-nondeterminism finding: the
    heartbeat jitter drew from the process-global RNG.  Same identity
    -> same pacing sequence (live chaos runs become replayable per
    node); the desynchronization ACROSS nodes that the jitter exists
    for comes from distinct ids."""

    async def go():
        net = InmemNetwork()
        keys = sorted([generate_key() for _ in range(2)],
                      key=lambda k: k.pub_hex)
        ts = [net.transport() for _ in keys]
        peers = [
            Peer(net_addr=t.local_addr(), pub_key_hex=k.pub_hex)
            for t, k in zip(ts, keys)
        ]
        n0 = Node(Config.test_config(), keys[0], peers, ts[0],
                  InmemAppProxy())
        n0b = Node(Config.test_config(), keys[0], peers,
                   net.transport(), InmemAppProxy())
        n1 = Node(Config.test_config(), keys[1], peers, ts[1],
                  InmemAppProxy())
        seq0 = [n0._random_timeout() for _ in range(10)]
        seq0b = [n0b._random_timeout() for _ in range(10)]
        seq1 = [n1._random_timeout() for _ in range(10)]
        assert seq0 == seq0b
        assert seq0 != seq1
        for n in (n0, n0b, n1):
            await n.shutdown()

    asyncio.run(go())


def test_service_debug_endpoints():
    """The pprof analogue on the service listener (reference piggy-backs Go
    pprof on /debug, cmd/main.go:26): stack dump, cProfile window, and the
    jax trace endpoint all answer on a live node."""
    import json
    import urllib.request

    from babble_tpu.service.service import Service

    async def go():
        net = InmemNetwork()
        key = generate_key()
        t = net.transport()
        peers = [Peer(net_addr=t.local_addr(), pub_key_hex=key.pub_hex)]
        node = Node(Config.test_config(), key, peers, t, InmemAppProxy())
        node.init()
        svc = Service("127.0.0.1:0", node)
        await svc.start()
        base = f"http://{svc.bind_addr}"
        loop = asyncio.get_running_loop()

        # generous socket timeout: the FIRST jax.profiler.start_trace
        # initializes the profiler session, measured >12 s on a cold
        # CPU backend in a contended container — the request is slow by
        # nature (the service runs it off-loop so the node stays live;
        # a 10 s timeout here was the tier-1 flake)
        def get(url):
            with urllib.request.urlopen(url, timeout=120) as r:
                return r.status, r.read()

        st, body = await loop.run_in_executor(None, get, base + "/Stats")
        assert st == 200 and b"consensus_events" in body
        st, body = await loop.run_in_executor(None, get, base + "/debug/stack")
        assert st == 200 and b"Thread" in body
        st, body = await loop.run_in_executor(
            None, get, base + "/debug/profile?seconds=0.2"
        )
        assert st == 200 and b"cumulative" in body
        st, body = await loop.run_in_executor(
            None, get, base + "/debug/trace?seconds=0.2"
        )
        assert st == 200
        assert json.loads(body)["trace_dir"]

        def get_bad(url):
            try:
                with urllib.request.urlopen(url, timeout=10) as r:
                    return r.status
            except urllib.error.HTTPError as e:
                return e.code

        st = await loop.run_in_executor(
            None, get_bad, base + "/debug/profile?seconds=abc"
        )
        assert st == 400
        await svc.close()
        await node.shutdown()

    asyncio.run(go())


@pytest.mark.slow
def test_fast_forward_rejoins_evicted_window():
    """A node whose Known falls below a peer's rolling window must catch up
    via the snapshot RPC and then keep committing alongside the fleet —
    the recovery the reference lacks entirely (a peer behind its rolling
    caches can never rejoin)."""

    async def go():
        # 4 participants: the 3 connected nodes still form a supermajority
        # (2n/3+1 = 3), so consensus + eviction proceed while one is down
        n = 4
        keys = sorted(
            [generate_key() for _ in range(n)], key=lambda k: k.pub_hex
        )
        peers_conf = []
        net = InmemNetwork()
        transports = [net.transport(f"inmem://{i}") for i in range(n)]
        for i, k in enumerate(keys):
            peers_conf.append(
                Peer(net_addr=transports[i].local_addr(), pub_key_hex=k.pub_hex)
            )
        # aggressive windows so eviction happens fast
        def conf():
            c = Config.test_config(heartbeat=0.01)
            c.cache_size = 64
            c.seq_window = 8
            return c

        proxies = [InmemAppProxy() for _ in range(n)]
        nodes = [
            Node(conf(), keys[i], peers_conf, transports[i], proxies[i])
            for i in range(n)
        ]
        for nd in nodes:
            nd.init()

        # partition the last node before it learns anything beyond roots
        straggler = n - 1
        net.disconnect_all(transports[straggler].local_addr())
        for nd in nodes[:straggler]:
            nd.run_task()

        # run the majority until they evicted past the straggler's Known
        deadline = asyncio.get_event_loop().time() + 240
        while asyncio.get_event_loop().time() < deadline:
            await asyncio.sleep(0.5)
            if all(nd.core.hg.dag.slot_base > 8 for nd in nodes[:straggler]):
                break
        assert all(
            nd.core.hg.dag.slot_base > 8 for nd in nodes[:straggler]
        ), "majority never evicted"

        # reconnect: the straggler's first syncs get too_late -> fast-forward
        for other in range(n):
            net.connect(transports[straggler].local_addr(),
                        transports[other].local_addr())
            net.connect(transports[other].local_addr(),
                        transports[straggler].local_addr())
        nodes[straggler].run_task()

        deadline = asyncio.get_event_loop().time() + 240
        ffed = False
        while asyncio.get_event_loop().time() < deadline:
            await asyncio.sleep(0.5)
            if nodes[straggler].core.hg.dag.slot_base > 0:
                ffed = True
                break
        assert ffed, "straggler never fast-forwarded"

        # and it must now make progress with the fleet
        base = nodes[straggler].core.hg.consensus_events_count()
        deadline = asyncio.get_event_loop().time() + 240
        while asyncio.get_event_loop().time() < deadline:
            await asyncio.sleep(0.5)
            if nodes[straggler].core.hg.consensus_events_count() > base + 10:
                break
        assert nodes[straggler].core.hg.consensus_events_count() > base + 10, (
            "rejoined node made no progress"
        )
        for nd in nodes:
            await nd.shutdown()

    asyncio.run(go())


def test_ff_snapshot_validation_rejects_foreign_membership_and_absurd_caps():
    """Catch-up trust covers ordering metadata only, never membership: a
    snapshot serving a different validator set (or absurd array capacities)
    must be rejected before Core.bootstrap (ADVICE r2 high)."""
    from babble_tpu.consensus.engine import TpuHashgraph

    async def go():
        net = InmemNetwork()
        key = generate_key()
        t = net.transport()
        peers = [Peer(net_addr=t.local_addr(), pub_key_hex=key.pub_hex)]
        node = Node(Config.test_config(), key, peers, t, InmemAppProxy())
        node.init()

        foreign = TpuHashgraph({generate_key().pub_hex: 0}, e_cap=64)
        with pytest.raises(ValueError, match="participant set"):
            node.validate_ff_snapshot(foreign)

        big = TpuHashgraph({key.pub_hex: 0}, e_cap=64)
        big.cfg = big.cfg._replace(e_cap=1 << 30)
        with pytest.raises(ValueError, match="capacities"):
            node.validate_ff_snapshot(big)

        ok = TpuHashgraph({key.pub_hex: 0}, e_cap=64)
        node.validate_ff_snapshot(ok)   # same membership, sane caps: passes
        await node.shutdown()

    asyncio.run(go())


def test_bootstrap_replays_local_tail_or_refuses():
    """A fast-forward snapshot that is *behind* our own published chain must
    not roll head/seq back (index reuse would read as equivocation).  The
    local tail is replayed into the new engine when insertable; otherwise
    bootstrap refuses and the old engine stays (ADVICE r2 medium)."""
    cores = _make_cores(3)
    c0, c1, c2 = cores

    # c1 learns c0's root, then c0 advances two self-events past that view
    _synchronize(c0, c1, [])
    c0.add_self_event([b"t1"])
    c0.add_self_event([b"t2"])
    assert c0.seq == 2
    head_before = c0.head

    snap = c1.hg   # knows c0 only up to seq 0
    c0.bootstrap(snap)
    assert c0.hg is snap
    assert c0.seq == 2 and c0.head == head_before, "tail must be replayed"
    # the replayed tail is actually in the adopted engine
    cid = c0.participants[c0.pub_hex]
    assert len(snap.dag.chains[cid]) == 3

    # refusal: c2's head is unknown to a fresh snapshot engine, so a tail
    # whose other-parent rides on c2 cannot be replayed there
    cores2 = _make_cores(3)
    d0, d1, d2 = cores2
    _synchronize(d0, d1, [])          # d1 knows d0's root only
    _synchronize(d2, d0, [])          # d0's new head has d2's root as parent
    old_engine = d0.hg
    old_head = d0.head
    old_ti = [
        (ev, ev.topological_index)
        for ev in old_engine.dag.events.window
    ]
    with pytest.raises(ValueError, match="not insertable"):
        d0.bootstrap(d1.hg)
    assert d0.hg is old_engine and d0.head == old_head
    for ev, ti in old_ti:
        assert ev.topological_index == ti, "gossip sort keys must survive"


def test_consensus_failure_is_recorded_apart_from_sync_errors():
    """A pipeline exception is kept on the node (the first one), so a
    driver fails on it instead of reading a sync-error count."""
    async def go():
        net = InmemNetwork()
        key = generate_key()
        t = net.transport()
        peers = [Peer(net_addr=t.local_addr(), pub_key_hex=key.pub_hex)]
        node = Node(Config.test_config(), key, peers, t, InmemAppProxy())
        node.init()
        boom = RuntimeError("device pipeline failed")

        def fail():
            raise boom

        node.core.run_consensus = fail
        with pytest.raises(RuntimeError):
            async with node.core_lock:
                await node._run_consensus_locked(0)
        assert node.consensus_error is boom
        await node.shutdown()

    asyncio.run(go())
