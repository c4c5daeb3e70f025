"""Array-native simulation path tests.

- native C++ graph builder == pure-Python twin, bit for bit;
- the zero-object array path (batch_from_arrays -> consensus step) produces
  the same rounds/order tensors as the Event-object engine path on the
  same DAG;
- schedule construction groups by level correctly at both backends.
"""

import functools

import numpy as np
import pytest

from babble_tpu import native
from babble_tpu.sim.arrays import (
    ArrayDag,
    batch_from_arrays,
    build_schedule,
    events_from_arrays,
    random_gossip_arrays,
)

FIELDS = ("sp", "op", "creator", "seq", "ts", "mbit", "levels")


@pytest.mark.parametrize("n,e,seed", [(4, 50, 0), (16, 800, 3), (64, 3000, 9)])
def test_native_matches_python(n, e, seed):
    a = random_gossip_arrays(n, e, seed=seed)
    b = random_gossip_arrays(n, e, seed=seed, force_python=True)
    for f in FIELDS:
        np.testing.assert_array_equal(
            getattr(a, f), getattr(b, f), err_msg=f
        )


def test_dag_invariants():
    dag = random_gossip_arrays(8, 500, seed=2)
    k = np.arange(dag.n_events)
    # parents precede children; levels strictly increase along edges
    assert (dag.sp < k).all() and (dag.op < k).all()
    nz = dag.sp >= 0
    assert (dag.levels[k[nz]] > dag.levels[dag.sp[nz]]).all()
    assert (dag.levels[k[nz]] > dag.levels[dag.op[nz]]).all()
    # self-parent chains: seq increments within creator
    assert (dag.creator[dag.sp[nz]] == dag.creator[k[nz]]).all()
    assert (dag.seq[dag.sp[nz]] + 1 == dag.seq[k[nz]]).all()


def test_build_schedule_levels():
    dag = random_gossip_arrays(8, 300, seed=4)
    sched = build_schedule(dag.levels)
    seen = sched[sched >= 0]
    assert sorted(seen.tolist()) == list(range(dag.n_events))
    for row in range(sched.shape[0]):
        lv = sched[row][sched[row] >= 0]
        assert (dag.levels[lv] == row).all()


@pytest.mark.parametrize("fd_mode", ["fast", "absorb", "incremental"])
def test_fd_modes_match_full(fd_mode):
    """Every selectable fd_mode of ingest_impl must produce bit-identical
    consensus tensors to the 'full' reference path.  Regression: 'absorb'
    once planted phantom la entries from sentinel-row junk (round-1 bug)."""
    import jax

    from babble_tpu.ops.state import (
        DagConfig, assert_consensus_parity, init_state,
    )
    from babble_tpu.parallel.sharded import consensus_step_impl

    n, e = 6, 300
    dag = random_gossip_arrays(n, e, seed=11)
    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 1, r_cap=32)
    batch = batch_from_arrays(dag)

    ref = jax.jit(functools.partial(consensus_step_impl, cfg, "full"))(
        init_state(cfg), batch
    )
    out = jax.jit(functools.partial(consensus_step_impl, cfg, fd_mode))(
        init_state(cfg), batch
    )
    assert_consensus_parity(ref, out, e, label=f"fd_mode={fd_mode}")


def test_array_path_matches_engine_path():
    """The zero-object batch must reach the same consensus tensors as the
    Event-object engine on an identical DAG.  (Coin-round mbit sources
    differ, but coin rounds require n undecided voting rounds — never hit
    at this size.)"""
    import jax

    from babble_tpu.consensus.engine import TpuHashgraph
    from babble_tpu.ops.state import DagConfig, init_state
    from babble_tpu.parallel.sharded import consensus_step_impl

    n, e = 8, 400
    dag = random_gossip_arrays(n, e, seed=6)

    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 1, r_cap=64)
    step = jax.jit(functools.partial(consensus_step_impl, cfg, "full"),
                   static_argnums=())
    out = step(init_state(cfg), batch_from_arrays(dag))

    events = events_from_arrays(dag)
    eng = TpuHashgraph(
        dag.participants(), verify_signatures=False,
        e_cap=e, s_cap=dag.max_chain + 1, r_cap=64,
    )
    for ev in events:
        eng.insert_event(ev)
    eng.run_consensus()

    np.testing.assert_array_equal(
        np.asarray(out.round)[:e], np.asarray(eng.state.round)[:e]
    )
    np.testing.assert_array_equal(
        np.asarray(out.witness)[:e], np.asarray(eng.state.witness)[:e]
    )
    np.testing.assert_array_equal(
        np.asarray(out.rr)[:e], np.asarray(eng.state.rr)[:e]
    )
    ordered = int(np.count_nonzero(np.asarray(out.rr)[:e] >= 0))
    assert ordered > 0


@pytest.mark.parametrize("n,e,seed", [(4, 200, 0), (8, 500, 3), (16, 1500, 9)])
def test_cpp_baseline_matches_tpu_engine(n, e, seed):
    """The C++ reference-algorithm baseline (bench denominator) must agree
    with the TPU pipeline on rounds, witnesses, round-received, consensus
    timestamps, and witness fame."""
    import functools

    import jax

    from babble_tpu.native import baseline_consensus
    from babble_tpu.ops.state import DagConfig, init_state
    from babble_tpu.parallel.sharded import consensus_step_impl

    dag = random_gossip_arrays(n, e, seed=seed)
    res = baseline_consensus(dag)
    assert res is not None, "toolchain is baked into the image"
    ordered, base = res
    assert ordered > 0

    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 1, r_cap=64)
    out = jax.jit(functools.partial(consensus_step_impl, cfg, "full"))(
        init_state(cfg), batch_from_arrays(dag)
    )
    np.testing.assert_array_equal(base["round"], np.asarray(out.round)[:e])
    np.testing.assert_array_equal(base["witness"], np.asarray(out.witness)[:e])
    np.testing.assert_array_equal(base["rr"], np.asarray(out.rr)[:e])
    recv = base["rr"] >= 0
    np.testing.assert_array_equal(
        base["cts"][recv], np.asarray(out.cts)[:e][recv]
    )
    assert int(recv.sum()) == ordered

    # fame trileans: engine's [R, N] wslot/famous table vs per-event fame
    wslot = np.asarray(out.wslot)
    famous = np.asarray(out.famous)
    for r in range(wslot.shape[0]):
        for j in range(n):
            s = int(wslot[r, j])
            if 0 <= s < e:
                assert base["fame"][s] == famous[r, j], (r, j, s)


def test_walk_mode_matches_fast():
    """The Pallas sequential-walk ingest (interpret mode on CPU) must be
    bit-identical to the XLA frontier path."""
    import jax

    from babble_tpu.ops.pallas_ingest import walk_supported
    from babble_tpu.ops.state import (
        DagConfig, assert_consensus_parity, init_state,
    )
    from babble_tpu.parallel.sharded import consensus_step_impl
    from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays

    n, e = 8, 1024
    dag = random_gossip_arrays(n, e, seed=13)
    batch = batch_from_arrays(dag)
    cfg = DagConfig(n=n, e_cap=e, s_cap=max(64, dag.max_chain + 1), r_cap=64)
    assert walk_supported(cfg.n, cfg.e_cap, cfg.s_cap)
    fast = jax.jit(lambda b: consensus_step_impl(cfg, "fast", init_state(cfg), b))(batch)
    walk = jax.jit(lambda b: consensus_step_impl(cfg, "walk", init_state(cfg), b))(batch)
    assert_consensus_parity(fast, walk, e, "walk-vs-fast")


def test_native_build_keyed_on_source_contents(tmp_path, monkeypatch):
    """A copied checkout carries mtimes that say nothing: the library is
    rebuilt whenever the .cpp contents change, never reused."""
    from babble_tpu import native

    monkeypatch.setattr(native, "_DIR", tmp_path)
    monkeypatch.setattr(native, "_BUILD", tmp_path / "_build")
    src = tmp_path / "probe.cpp"
    src.write_text('extern "C" int probe() { return 1; }\n')
    first = native.build_path("probe")
    assert native.build_path("probe") == first
    assert native._load_lib("probe").probe() == 1
    src.write_text('extern "C" int probe() { return 2; }\n')
    assert native.build_path("probe") != first
    assert native._load_lib("probe").probe() == 2
