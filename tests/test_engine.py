"""TPU array engine: fixture parity + differential tests against the oracle.

The differential suite is the core correctness argument (SURVEY.md §4
implications): random gossip DAGs at several sizes/shapes are run through
both engines and every observable — rounds, witnesses, fame, round-received,
consensus timestamps, final order — must match exactly.
"""

import numpy as np
import pytest

from babble_tpu.consensus.engine import TpuHashgraph
from babble_tpu.consensus.oracle import OracleHashgraph
from babble_tpu.sim import random_gossip_dag
from babble_tpu.store.inmem import InmemStore

from .fixtures import consensus_fixture, round_fixture, simple_fixture


def engine_from_fixture(fx, **kw) -> TpuHashgraph:
    h = TpuHashgraph(fx.participants, e_cap=64, s_cap=16, r_cap=16, **kw)
    for ev in fx.ordered_events:
        h.insert_event(ev)
    return h


class TestEngineFixtures:
    @pytest.fixture(scope="class")
    def simple(self):
        fx = simple_fixture()
        return engine_from_fixture(fx), fx.index

    def test_ancestor(self, simple):
        h, idx = simple
        assert h.ancestor(idx["e01"], idx["e0"])
        assert h.ancestor(idx["e20"], idx["e01"])
        assert h.ancestor(idx["e12"], idx["e20"])
        assert h.ancestor(idx["e12"], idx["e0"])
        assert not h.ancestor(idx["e01"], idx["e2"])

    def test_strongly_see_and_rounds(self):
        fx = round_fixture()
        h = engine_from_fixture(fx)
        idx = fx.index
        assert h.strongly_see(idx["e21"], idx["e0"])
        assert h.strongly_see(idx["e02"], idx["e10"])
        assert h.strongly_see(idx["f1"], idx["e2"])
        assert not h.strongly_see(idx["e10"], idx["e0"])
        assert not h.strongly_see(idx["e21"], idx["e2"])
        assert not h.strongly_see(idx["f1"], idx["e02"])

        assert h.round(idx["e0"]) == 0
        assert h.round(idx["e02"]) == 0
        assert h.round(idx["f1"]) == 1
        assert h.witness(idx["e0"]) and h.witness(idx["f1"])
        assert not h.witness(idx["e10"]) and not h.witness(idx["e02"])
        assert h.rounds() == 2
        assert sorted(map(fx.name_of, h.round_witnesses(0))) == ["e0", "e1", "e2"]
        assert [fx.name_of(w) for w in h.round_witnesses(1)] == ["f1"]

    def test_consensus_pipeline(self):
        fx = consensus_fixture()
        h = engine_from_fixture(fx)
        idx = fx.index
        committed = []
        h.commit_callback = committed.extend
        h.run_consensus()

        assert h.round(idx["g0"]) == 2
        assert h.round(idx["g1"]) == 2
        assert h.round(idx["g2"]) == 2
        for name in ("e0", "e1", "e2"):
            assert h.famous_of(0, idx[name]) is True

        for name, hex_id in idx.items():
            if name.startswith("e"):
                ev = h.dag.events[h.dag.slot_of[hex_id]]
                assert ev.round_received == 1, name

        consensus = [fx.name_of(x) for x in h.consensus_events()]
        assert len(consensus) == 6
        expected1 = ["e0", "e10", "e1", "e21", "e2", "e02"]
        expected2 = ["e0", "e1", "e10", "e2", "e21", "e02"]
        for i, name in enumerate(consensus):
            assert name in (expected1[i], expected2[i]), consensus
        assert [e.hex() for e in committed] == [
            idx[n] for n in consensus
        ]

    def test_oldest_self_ancestor_to_see(self):
        fx = consensus_fixture()
        h = engine_from_fixture(fx)
        idx = fx.index
        assert h.oldest_self_ancestor_to_see(idx["f0"], idx["e1"]) == idx["e02"]
        assert h.oldest_self_ancestor_to_see(idx["f1"], idx["e0"]) == idx["e10"]
        assert h.oldest_self_ancestor_to_see(idx["e21"], idx["e1"]) == idx["e21"]
        assert h.oldest_self_ancestor_to_see(idx["e2"], idx["e1"]) == ""

    def test_fork_rejection(self):
        from babble_tpu.core.dag import InsertError
        from babble_tpu.core.event import new_event

        fx = simple_fixture()
        h = engine_from_fixture(fx)
        fork = new_event([b"yo"], ("", ""), fx.nodes[2].pub, 0)
        fork.sign(fx.nodes[2].key)
        with pytest.raises(InsertError):
            h.insert_event(fork)


# ----------------------------------------------------------------------
# differential: oracle vs engine on random gossip DAGs


def _oracle_for(dag) -> OracleHashgraph:
    store = InmemStore(dag.participants, cache_size=100_000)
    return OracleHashgraph(
        participants=dag.participants, store=store, verify_signatures=False
    )


def _engine_for(dag, **kw) -> TpuHashgraph:
    return TpuHashgraph(dag.participants, verify_signatures=False, **kw)


def _insert_both(oracle, engine, ev):
    """Distinct Event instances per engine — both engines mutate
    round_received/consensus_timestamp in place, so sharing one object would
    make the differential assertions tautological."""
    oracle.insert_event(ev.clone())
    engine.insert_event(ev.clone())


def _compare_all(dag, oracle, engine):
    # rounds/witness per event
    for ev in dag.events:
        x = ev.hex()
        assert engine.round(x) == oracle.round(x), f"round mismatch {x[:12]}"
        assert engine.witness(x) == oracle.witness(x), f"witness mismatch {x[:12]}"

    # fame per round witness
    for r in range(oracle.store.rounds()):
        info = oracle.store.get_round(r)
        for w in info.witnesses():
            o_fame = info.events[w].famous
            e_fame = engine.famous_of(r, w)
            assert e_fame == o_fame, f"fame mismatch round {r} {w[:12]}"

    # round received + consensus timestamps
    for ev in dag.events:
        o_ev = oracle.store.get_event(ev.hex())
        e_ev = engine.dag.events[engine.dag.slot_of[ev.hex()]]
        assert e_ev.round_received == o_ev.round_received, ev.hex()[:12]
        if o_ev.round_received is not None:
            assert e_ev.consensus_timestamp == o_ev.consensus_timestamp, (
                ev.hex()[:12]
            )

    # final order
    assert engine.consensus_events() == oracle.consensus_events()
    assert engine.consensus_transactions == oracle.consensus_transactions
    assert engine.last_consensus_round == oracle.last_consensus_round


@pytest.mark.parametrize(
    "n,n_events,seed,grain",
    [
        (3, 60, 0, 1_000),
        (4, 150, 1, 1_000),
        (5, 200, 2, 1_000),
        (6, 200, 3, 1_000),
        (4, 150, 4, 1),          # ns-granular ties unlikely
        (4, 150, 5, 10_000_000), # coarse: median-timestamp ties common
        (7, 250, 6, 1_000),
    ],
)
def test_differential_batch(n, n_events, seed, grain):
    """Single big batch: ingest everything, one consensus call each."""
    dag = random_gossip_dag(n, n_events, seed=seed, ts_granularity_ns=grain)
    oracle = _oracle_for(dag)
    engine = _engine_for(dag, e_cap=512, s_cap=128, r_cap=64)
    for ev in dag.events:
        _insert_both(oracle, engine, ev)
    oracle.divide_rounds()
    oracle.decide_fame()
    oracle.find_order()
    engine.run_consensus()
    _compare_all(dag, oracle, engine)


@pytest.mark.parametrize("n,n_events,seed,chunk", [(4, 160, 10, 7), (5, 200, 11, 13)])
def test_differential_incremental(n, n_events, seed, chunk):
    """Chunked ingestion with consensus between chunks — the live gossip
    shape.  Must converge to the same totals as the oracle run the same way."""
    dag = random_gossip_dag(n, n_events, seed=seed)
    oracle = _oracle_for(dag)
    engine = _engine_for(dag, e_cap=256, s_cap=64, r_cap=32)
    for i, ev in enumerate(dag.events):
        _insert_both(oracle, engine, ev)
        if (i + 1) % chunk == 0:
            oracle.divide_rounds()
            oracle.decide_fame()
            oracle.find_order()
            engine.run_consensus()
    oracle.divide_rounds()
    oracle.decide_fame()
    oracle.find_order()
    engine.run_consensus()
    _compare_all(dag, oracle, engine)


def test_engine_capacity_growth():
    """Start tiny, force e/s/r growth, verify results still match."""
    dag = random_gossip_dag(4, 120, seed=20)
    oracle = _oracle_for(dag)
    engine = _engine_for(dag, e_cap=16, s_cap=4, r_cap=4)
    for ev in dag.events:
        _insert_both(oracle, engine, ev)
    oracle.divide_rounds()
    oracle.decide_fame()
    oracle.find_order()
    engine.run_consensus()
    assert engine.cfg.e_cap >= 120
    _compare_all(dag, oracle, engine)


def _assert_fd_full_equals_incremental(n, e, seed, e_cap, s_cap, r_cap):
    dag = random_gossip_dag(n, e, seed=seed)
    e_inc = _engine_for(dag, e_cap=e_cap, s_cap=s_cap, r_cap=r_cap)
    e_full = _engine_for(dag, e_cap=e_cap, s_cap=s_cap, r_cap=r_cap)
    for ev in dag.events:
        e_inc.insert_event(ev)
        e_full.insert_event(ev)
    # incremental path: small chunks
    import babble_tpu.consensus.engine as eng_mod

    old = eng_mod._FD_FULL_THRESHOLD
    try:
        eng_mod._FD_FULL_THRESHOLD = 10**9
        e_inc.flush()
        eng_mod._FD_FULL_THRESHOLD = 0
        e_full.flush()
    finally:
        eng_mod._FD_FULL_THRESHOLD = old
    np.testing.assert_array_equal(
        np.asarray(e_inc.state.fd), np.asarray(e_full.state.fd)
    )
    np.testing.assert_array_equal(
        np.asarray(e_inc.state.la), np.asarray(e_full.state.la)
    )
    return e_full


def test_fd_full_equals_incremental():
    """The two first-descendant strategies must produce identical tensors."""
    _assert_fd_full_equals_incremental(5, 100, 30, 128, 64, 32)


def test_fd_full_equals_incremental_several_blocks():
    """The same at chains of ~650 events, several 128-position blocks of
    the chain view, where the full fill searches in both of its levels."""
    from babble_tpu.ops.state import SORTED_BLOCK

    eng = _assert_fd_full_equals_incremental(4, 2600, 31, 4096, 1024, 256)
    assert int(np.asarray(eng.state.cnt).max()) > 4 * SORTED_BLOCK
