"""The fork yardstick at small sizes: the fork-aware reference
(reference/fork_consensus.cpp) agrees with the program's definition-first
oracle (consensus/byzantine.ForkOracle) on DAGs with forks and with the
upstream reference (reference/consensus.cpp) on fork-free ones; the fork
generator gives every seed the same sizes; both controls fail the
comparison that decides `correct`, and a whole run of the fork cell
comes out correct, and not correct with its answer altered."""

import os

import numpy as np
import pytest

from benchmark.reference import fork_native, hashgraph, native

SEEDS = (3, 11, 2**31 + 5, 2**33 + 17)
FIELDS = ("sp", "op", "creator", "seq", "ts", "mbit", "levels")
CELL = "testnet4_byz.full"
#: the fork cell's configuration cut to a CPU test's size
SMALL = {"events": 2048, "r_cap": 256, "s_cap": 513, "sched_rows": 1300}


def _decisions(fo, events) -> dict:
    """ForkOracle ``fo``'s decisions on ``events``, as the reference's
    arrays."""
    hx = [ev.hex() for ev in events]

    def fame(h):
        if not fo.witness(h):
            return -1
        return {None: 0, True: 1, False: 2}[fo.famous[h]]

    return {"round": np.array([fo.round(h) for h in hx], np.int32),
            "witness": np.array([fo.witness(h) for h in hx]),
            "fame": np.array([fame(h) for h in hx], np.int8),
            "rr": np.array([fo.rr.get(h, -1) for h in hx], np.int32),
            "cts": np.array([fo.cts.get(h, 0) for h in hx], np.int64)}


def _oracle(dag: dict, n: int):
    """ForkOracle's decisions on the DAG's events, and the events' coin
    bits (the oracle reads them from the event hashes)."""
    from babble_tpu.consensus.byzantine import ForkOracle
    from babble_tpu.sim.arrays import ArrayDag, events_from_arrays

    adag = ArrayDag(n, *(dag[k] for k in FIELDS), 0)
    events = events_from_arrays(adag)
    fo = ForkOracle(adag.participants())
    for ev in events:
        fo.insert_event(ev)
    fo.run_consensus()
    return _decisions(fo, events), np.array([ev.middle_bit()
                                             for ev in events])


def _arrays_of(events, participants) -> dict:
    """A DAG of Event objects (insertion order) as the reference's
    arrays."""
    slot = {ev.hex(): i for i, ev in enumerate(events)}
    return {
        "sp": np.array([slot.get(ev.self_parent, -1) for ev in events],
                       np.int32),
        "op": np.array([slot.get(ev.other_parent, -1) for ev in events],
                       np.int32),
        "creator": np.array([participants[ev.creator] for ev in events],
                            np.int32),
        "seq": np.array([ev.index for ev in events], np.int32),
        "ts": np.array([ev.body.timestamp for ev in events], np.int64),
        "mbit": np.array([ev.middle_bit() for ev in events]),
    }


def _agrees(ref: dict, got: dict, e: int) -> None:
    assert hashgraph.mismatches(ref, got, e) == dict(
        round=0, witness=0, fame=0, rr=0, cts=0)


@pytest.mark.parametrize("n,e,forkers,seed", [
    (4, 400, 1, s) for s in SEEDS] + [(4, 600, 1, 7), (7, 560, 2, 5),
                                       (7, 560, 2, 2**31 + 9)])
def test_reference_agrees_with_fork_oracle(n, e, forkers, seed):
    dag = fork_native.fork_dag(n, e, seed, forkers)
    got, mbit = _oracle(dag, n)
    ordered, ref = fork_native.consensus(dict(dag, mbit=mbit), n)
    _agrees(ref, got, e)
    assert ordered > e // 2
    # the forkers' late events are never ordered: their forks are seen
    late = (np.isin(dag["creator"], dag["forkers"])
            & (dag["seq"] > 3 * (e // n) // 4))
    assert late.any() and (ref["rr"][late] < 0).all()


@pytest.mark.parametrize("n,e,rate,seed", [(4, 300, 0.08, 1), (6, 360, 0.06, 3),
                                           (9, 450, 0.05, 21)])
def test_reference_agrees_with_fork_oracle_on_random_forks(n, e, rate, seed):
    """On the program's own byzantine generator (forks off random earlier
    events, branches that may never be synced)."""
    from babble_tpu.consensus.byzantine import ForkOracle
    from babble_tpu.sim import random_byzantine_dag

    dag = random_byzantine_dag(n, e, seed=seed, fork_rate=rate)
    fo = ForkOracle(dag.participants)
    for ev in dag.events:
        fo.insert_event(ev.clone())
    fo.run_consensus()
    arrays = _arrays_of(dag.events, dag.participants)
    assert len({(c, s) for c, s in zip(arrays["creator"], arrays["seq"])}) < e
    _, ref = fork_native.consensus(arrays, n)
    _agrees(ref, _decisions(fo, dag.events), e)


@pytest.mark.parametrize("n,e,seed", [(4, 2000, s) for s in SEEDS[:2]]
                         + [(16, 3000, 11)])
def test_reference_equals_upstream_reference_without_forks(n, e, seed):
    for dag in (native.gossip_dag(n, e, seed),
                fork_native.fork_dag(n, e, seed, forkers=0)):
        _, up = native.consensus(dag, n)
        _, ref = fork_native.consensus(dag, n)
        _agrees(up, ref, e)
        _, blind = fork_native.consensus(dag, n, fork_blind=True)
        _agrees(up, blind, e)


@pytest.mark.parametrize("n,e,forkers", [(4, 2048, 1), (4, 2050, 1),
                                         (7, 2800, 2)])
def test_fork_generator_gives_every_seed_the_same_sizes(n, e, forkers):
    sizes = set()
    for seed in SEEDS:
        dag = fork_native.fork_dag(n, e, seed, forkers)
        counts = np.bincount(dag["creator"], minlength=n)
        assert counts.max() - counts.min() <= 1
        assert np.all(dag["sp"][n:] < np.arange(n, e))
        assert np.all(dag["op"][n:] < np.arange(n, e))
        assert len(set(dag["forkers"].tolist())) == forkers
        twins = []
        for f in dag["forkers"]:
            seqs, c = np.unique(dag["seq"][dag["creator"] == f],
                                return_counts=True)
            assert (c > 1).sum() == 1           # one equivocation
            twins.append(int(seqs[c > 1][0]))
            # the orphaned twin is gossiped, the other extended
            pair = np.flatnonzero((dag["creator"] == f)
                                  & (dag["seq"] == twins[-1]))
            assert np.isin(pair, dag["op"]).any()
        sizes.add((int(dag["seq"].max()), tuple(sorted(twins))))
    assert len(sizes) == 1
    a, b = (fork_native.fork_dag(n, e, s, forkers) for s in SEEDS[:2])
    assert not np.array_equal(a["creator"], b["creator"])


def test_controls_fail_the_comparison():
    dag = fork_native.fork_dag(4, 2048, 3, 1)
    _, ref = fork_native.consensus(dag, 4)
    _, mean = fork_native.consensus(dag, 4, ts_rule=1)
    bad = hashgraph.mismatches(ref, mean, 2048)
    assert bad["round"] == bad["witness"] == bad["fame"] == bad["rr"] == 0
    assert bad["cts"] > 2048 // 4
    _, blind = fork_native.consensus(dag, 4, fork_blind=True)
    bad = hashgraph.mismatches(ref, blind, 2048)
    # the forker's events count, and are ordered, once its fork is seen
    assert bad["round"] > 0 and bad["rr"] > 0
    assert hashgraph.events_differing(ref, blind, 2048) > 2048 // 8


@pytest.fixture
def small_byz(monkeypatch):
    """Skip the harness's look for a chip and cut the fork cell's
    configuration to SMALL."""
    from benchmark import common, run

    load = common.load_json

    def load_small(path):
        data = load(path)
        if path.endswith(os.path.join("configs", "testnet4_byz.json")):
            data.update(SMALL)
        return data

    monkeypatch.setattr(run, "_tpu_devices", lambda chips: None)
    monkeypatch.setattr(common, "load_json", load_small)


def test_fork_cell_run_is_correct_and_controls_are_not(small_byz):
    from benchmark import common, run

    res = run.run_cell(CELL, 2**31 + 99, 1.0, False)
    assert res["attempted"] > 0
    assert res["correct"], res["checks"]
    assert res["metrics"]["replay_events_per_s"]["value"] > 0
    driver = common.import_file(os.path.join(
        common.HERE, "drivers", "replay_fork.py"), "replay_fork_test")
    ctl = driver.control_readings(res["_internals"])
    assert set(ctl) == {"events_differing.ts_rule_1",
                        "events_differing.fork_blind"}
    assert all(v > 0 for v in ctl.values()), ctl


@pytest.mark.parametrize("field", ["rr", "cts", "round"])
def test_fork_cell_altered_answer_is_not_correct(small_byz, monkeypatch,
                                                 field):
    import jax

    import babble_tpu.cli as cli
    from benchmark import run

    real_step = cli.sim_step

    def broken(dag, r_cap, mode="fast"):
        cfg, step = real_step(dag, r_cap, mode)
        return cfg, jax.jit(lambda b: (lambda o: o._replace(**{
            field: getattr(o, field).at[5].add(1)}))(step(b)))

    monkeypatch.setattr(cli, "sim_step", broken)
    res = run.run_cell(CELL, 2**31 + 99, 0.5, False)
    assert res["attempted"] > 0
    assert not res["correct"]
    assert res["checks"]["events_differing"]["value"] >= 1
