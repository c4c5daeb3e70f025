"""The batch path on a DAG that holds an equivocation: ``cli.sim_step``
observes the fork in the DAG and runs the fork-aware pipeline
(``ops/forks.fork_pipeline_impl``, the function ``ForkHashgraph`` runs
live), its branch columns assigned by ``ForkDag``'s rules through
``ops/forks.ForkArrays``.  Checked event by event against the
benchmark's fork-aware plain reference on DAGs from the benchmark's fork
generator (``benchmark/reference``)."""

import jax
import numpy as np
import pytest

from babble_tpu.cli import sim_inputs, sim_step
from babble_tpu.ops.forks import (
    ForkArrays, ForkBudgetError, ForkConfig, ForkDag, ForkOut,
)
from babble_tpu.ops.state import DagConfig, DagState
from babble_tpu.sim.arrays import (
    ArrayDag, events_from_arrays, random_gossip_arrays,
)

FIELDS = ("sp", "op", "creator", "seq", "ts", "mbit", "levels")
#: 4 x 2,048: rounds under 140 and levels under 1,300 on every seed
R_CAP, SCHED_ROWS = 256, 1300


def _forked(n: int, e: int, seed: int, forkers: int = 1):
    from benchmark.reference import fork_native

    dag = fork_native.fork_dag(n, e, seed, forkers)
    return dag, ArrayDag(n, *(dag[k] for k in FIELDS), seed)


@pytest.mark.parametrize("seed", [3, 11, 2**31 + 5, 2**33 + 17])
def test_batch_entry_agrees_with_fork_reference(seed):
    from benchmark.reference import fork_native, hashgraph

    dag, adag = _forked(4, 2048, seed)
    cfg, step = sim_step(adag, R_CAP)
    out = jax.block_until_ready(step(*sim_inputs(adag, cfg, SCHED_ROWS)))
    e = 2048
    got = {"round": np.asarray(out.round), "witness": np.asarray(out.witness),
           "fame": hashgraph.fame_per_event(np.asarray(out.wslot),
                                            np.asarray(out.famous), e),
           "rr": np.asarray(out.rr), "cts": np.asarray(out.cts)}
    ordered, ref = fork_native.consensus(dag, 4)
    assert hashgraph.mismatches(ref, got, e) == dict(
        round=0, witness=0, fame=0, rr=0, cts=0)
    assert hashgraph.events_differing(ref, got, e) == 0
    assert int(np.count_nonzero(got["rr"][:e] >= 0)) == ordered > e // 2
    assert int(out.max_round) < R_CAP - 1


def test_sim_step_picks_the_pipeline_from_the_dag():
    _, forked = _forked(4, 512, 7)
    assert forked.branch_slots == 2
    cfg, step = sim_step(forked, 64)
    assert isinstance(cfg, ForkConfig) and cfg.k == 2
    out = step(*sim_inputs(forked, cfg))
    assert isinstance(out, ForkOut)

    honest = random_gossip_arrays(4, 512, seed=7)
    assert honest.branch_slots == 1
    cfg, step = sim_step(honest, 64)
    assert isinstance(cfg, DagConfig)
    out = step(*sim_inputs(honest, cfg))
    assert isinstance(out, DagState)


def test_fork_pipeline_counts_its_closure_and_vote_steps():
    _, adag = _forked(4, 2048, 2**31 + 7)
    cfg, step = sim_step(adag, R_CAP)
    out = step(*sim_inputs(adag, cfg, SCHED_ROWS))
    max_round = int(out.max_round)
    # one closure pass at least for each round assigned, and fame's
    # diagonal scan runs every diagonal from 2 to max_round
    assert int(out.closure_steps) >= max_round + 1 > 1
    assert int(out.vote_steps) == max_round - 1 > 0


def test_fork_arrays_follow_fork_dag():
    """The arrays' branch columns, chains and batch are those of
    inserting every event into a ForkDag."""
    _, adag = _forked(4, 600, 5)
    events = events_from_arrays(adag)
    fd = ForkDag(adag.participants(), k=2)
    for ev in events:
        fd.insert(ev)
    cfg = ForkConfig(n=4, k=2, e_cap=600, s_cap=adag.max_chain + 1,
                     r_cap=64)
    want = fd.build_batch(cfg)
    lay = ForkArrays(4, 2, adag.sp, adag.op, adag.creator, adag.seq,
                     adag.ts)
    got = lay.build_batch(cfg, [ev.middle_bit() for ev in events],
                          np.asarray(want.sched))
    assert lay.ebr == fd.ebr and lay.br_div == fd.br_div
    for name in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)


def test_fork_arrays_refuse_a_fork_past_the_budget():
    _, adag = _forked(7, 700, 5, forkers=2)
    assert adag.branch_slots == 2
    ForkArrays(7, 2, adag.sp, adag.op, adag.creator, adag.seq, adag.ts)
    with pytest.raises(ForkBudgetError):
        ForkArrays(7, 1, adag.sp, adag.op, adag.creator, adag.seq, adag.ts)
