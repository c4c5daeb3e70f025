"""The yardstick at small sizes: the generator gives every seed the same
sizes, the program agrees with the reference on DAGs drawn from several
seeds, and the control (the reference with mean consensus timestamps in
the program's place) fails the comparison that decides `correct`."""

import numpy as np
import pytest

from benchmark.reference import hashgraph, native

SEEDS = (3, 11, 2**31 + 5, 2**33 + 17)


@pytest.mark.parametrize("n,e", [(4, 2000), (4, 2003), (16, 3000)])
def test_generator_gives_every_seed_the_same_sizes(n, e):
    chains = set()
    for seed in SEEDS:
        dag = native.gossip_dag(n, e, seed)
        counts = np.bincount(dag["creator"], minlength=n)
        assert counts.max() - counts.min() <= 1
        assert np.all(dag["sp"][n:] < np.arange(n, e))
        assert np.all(dag["op"][n:] < np.arange(n, e))
        chains.add(int(dag["seq"].max()))
    assert len(chains) == 1
    a, b = native.gossip_dag(n, e, SEEDS[0]), native.gossip_dag(n, e, SEEDS[1])
    assert not np.array_equal(a["creator"], b["creator"])


@pytest.mark.parametrize("n,e,r_cap,seed", [
    (4, 2000, 256, s) for s in SEEDS] + [(16, 3000, 64, 11)])
def test_program_batch_step_agrees_with_reference(n, e, r_cap, seed):
    import jax

    from babble_tpu.cli import sim_step
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import ArrayDag, batch_from_arrays

    dag = native.gossip_dag(n, e, seed)
    adag = ArrayDag(n, *(dag[k] for k in ("sp", "op", "creator", "seq",
                                          "ts", "mbit", "levels")), seed)
    cfg, step = sim_step(adag, r_cap, "fast")
    out = jax.block_until_ready(step(init_state(cfg), batch_from_arrays(adag)))
    got = {"round": np.asarray(out.round), "witness": np.asarray(out.witness),
           "fame": hashgraph.fame_per_event(np.asarray(out.wslot),
                                            np.asarray(out.famous), e),
           "rr": np.asarray(out.rr), "cts": np.asarray(out.cts)}
    _, ref = native.consensus(dag, n)
    assert (ref["rr"] >= 0).sum() > e // 2
    assert hashgraph.mismatches(ref, got, e) == dict(
        round=0, witness=0, fame=0, rr=0, cts=0)
    assert hashgraph.events_differing(ref, got, e) == 0


@pytest.mark.parametrize("n,e", [(4, 2000), (16, 3000)])
def test_control_fails_the_comparison(n, e):
    dag = native.gossip_dag(n, e, 3)
    _, ref = native.consensus(dag, n)
    _, ctl = native.consensus(dag, n, ts_rule=1)
    bad = hashgraph.mismatches(ref, ctl, e)
    assert bad["round"] == bad["witness"] == bad["fame"] == bad["rr"] == 0
    assert bad["cts"] > e // 4
    assert hashgraph.events_differing(ref, ctl, e) == bad["cts"]


def test_calibrate_reads_program_below_control(small_cell, capsys):
    import json

    from benchmark import calibrate

    calibrate.main(["--workload", "testnet4.full", "--seeds", "5,2147483999",
                    "--seconds", "0.5"])
    line = [x for x in capsys.readouterr().out.splitlines()
            if x.startswith("SUMMARY")][-1]
    summary = json.loads(line.split(" ", 1)[1])
    assert all(v == 0 for v in summary["lower"].values())
    assert summary["upper"]["events_differing"] > 0
