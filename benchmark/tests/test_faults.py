"""A run with the timed path broken underneath has to come out not
correct.  Each test skips the harness's look for a chip and drives the
rest of a run (set-up, window, comparison) at a small size on the CPU,
once for each fault the cell can have:

- a step that returns its state unchanged;
- half of the batch left out;
- an answer altered where it is produced.

The cell runs on one chip, so no exchange between chips exists to be
left out."""

import pytest

from benchmark import run

CELL = "testnet4.full"
SEED = 2**31 + 99


def test_sound_run_is_correct(small_cell):
    res = run.run_cell(CELL, SEED, 1.0, False)
    assert res["attempted"] > 0
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault,differing", [
    ("unchanged", 2000), ("half", 1000), ("altered", 1)])
def test_fault_is_not_correct(small_cell, monkeypatch, fault, differing):
    import jax

    import babble_tpu.cli as cli
    import babble_tpu.sim.arrays as arrays

    real_step, real_batch = cli.sim_step, arrays.batch_from_arrays
    if fault == "half":
        def half_batch(dag, bucket=None):
            k = dag.n_events // 2
            half = arrays.ArrayDag(dag.n, *(getattr(dag, f)[:k] for f in (
                "sp", "op", "creator", "seq", "ts", "mbit", "levels")),
                dag.seed)
            return real_batch(half, bucket=lambda _: dag.n_events)
        monkeypatch.setattr(arrays, "batch_from_arrays", half_batch)
    else:
        def broken(dag, r_cap, mode="fast"):
            cfg, step = real_step(dag, r_cap, mode)
            if fault == "unchanged":
                return cfg, jax.jit(lambda state, batch: state)
            return cfg, jax.jit(lambda state, batch: (lambda o: o._replace(
                rr=o.rr.at[0].add(1)))(step(state, batch)))
        monkeypatch.setattr(cli, "sim_step", broken)
    res = run.run_cell(CELL, SEED, 1.0, False)
    assert res["attempted"] > 0
    assert not res["correct"]
    # every event, the left-out half, the one altered event
    assert res["checks"]["events_differing"]["value"] >= differing, \
        res["checks"]
