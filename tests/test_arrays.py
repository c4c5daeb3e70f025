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


def _assert_fd_mode_matches_full(fd_mode, n, e, seed, r_cap):
    import jax

    from babble_tpu.ops.state import (
        DagConfig, assert_consensus_parity, init_state,
    )
    from babble_tpu.parallel.sharded import consensus_step_impl

    dag = random_gossip_arrays(n, e, seed=seed)
    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 1, r_cap=r_cap)
    batch = batch_from_arrays(dag)

    ref = jax.jit(functools.partial(consensus_step_impl, cfg, "full"))(
        init_state(cfg), batch
    )
    out = jax.jit(functools.partial(consensus_step_impl, cfg, fd_mode))(
        init_state(cfg), batch
    )
    assert_consensus_parity(ref, out, e, label=f"fd_mode={fd_mode}")
    return cfg


@pytest.mark.parametrize("fd_mode", ["fast", "incremental"])
def test_fd_modes_match_full(fd_mode):
    """Every selectable fd_mode of ingest_impl must produce bit-identical
    consensus tensors to the 'full' reference path ('walk' is checked by
    test_walk_mode_matches_fast)."""
    _assert_fd_mode_matches_full(fd_mode, 6, 300, 11, 32)


@pytest.mark.parametrize("fd_mode", ["fast", "incremental"])
def test_fd_modes_match_full_several_blocks(fd_mode):
    """The same at chains of ~650 events, several 128-position blocks of
    the chain view, where 'full' searches in both of its levels.  At
    this shape 'fast' picks the same search itself
    (``fd_reverse_scan_wins`` is False), so [fast] checks the rest of
    its path;
    [incremental] is the independent check of the search."""
    from babble_tpu.ops.state import SORTED_BLOCK

    cfg = _assert_fd_mode_matches_full(fd_mode, 4, 2600, 5, 256)
    assert cfg.s_cap + 1 > 4 * SORTED_BLOCK


def _plain_count(V, thr):
    return (V[:, :, :, None] < thr[None, None]).sum(axis=1)


@pytest.mark.parametrize("s1", [1, 127, 128, 129, 256, 700])
@pytest.mark.parametrize("offset", [False, True])
def test_count_below_sorted_matches_plain_count(s1, offset):
    """The two-level search over the sorted chain view equals the plain
    compare-count: non-decreasing columns holding -1 entries and an
    INT32_MAX tail past a drawn length (one column full, so that at 256
    a threshold lies past every block), thresholds t + s_off."""
    import jax

    from babble_tpu.ops.state import INT32_MAX, count_below_sorted

    rng = np.random.default_rng(s1 * 2 + offset)
    c, d = 3, 5
    V = np.sort(rng.integers(-1, s1 + 4, (c, s1, d)), axis=1)
    cnt = rng.integers(0, s1 + 1, c)
    cnt[0] = s1
    V = np.where(np.arange(s1)[None, :, None] < cnt[:, None, None], V,
                 INT32_MAX).astype(np.int32)
    s_off = rng.integers(1, 6, d) if offset else np.zeros(d, int)
    thr = (np.arange(s1 + 1)[None, :] + s_off[:, None]).astype(np.int32)
    got = np.asarray(jax.jit(count_below_sorted)(V, thr))
    np.testing.assert_array_equal(got, _plain_count(V, thr))


@pytest.mark.parametrize("s1", [100, 700])
def test_count_below_sorted_in_chunks(monkeypatch, s1):
    """The same with the threshold axis cut into many chunks, a last one
    partly padding (a column of one block, 100, and of six, 700)."""
    import jax

    from babble_tpu.ops import state
    from babble_tpu.ops.state import count_below_sorted

    rng = np.random.default_rng(s1)
    c, d = 2, 3
    V = np.sort(rng.integers(-1, s1, (c, s1, d)), axis=1).astype(np.int32)
    thr = (np.arange(s1 + 3)[None, :] + np.arange(d)[:, None]).astype(
        np.int32)
    monkeypatch.setattr(state, "_COUNT_CHUNK_ELEMS", 7 * c * d * s1)
    got = np.asarray(jax.jit(lambda v, t: count_below_sorted(v, t))(V, thr))
    np.testing.assert_array_equal(got, _plain_count(V, thr))


@pytest.mark.parametrize("k", [1, 2])
def test_both_cells_take_the_chain_count(k):
    """Both benchmark cells' shapes (38,912 schedule rows, 65,536 events;
    the fork cell k = 2, the honest shape k = 1) run the chain-view
    count, not the reverse scan."""
    from babble_tpu.ops.state import fd_reverse_scan_wins

    assert not fd_reverse_scan_wins(38_912, 65_536, k)


@pytest.mark.parametrize("fd_mode", ["absorb", "fsat"])
def test_ingest_rejects_an_unknown_fd_mode(fd_mode):
    """A mode outside ingest.FD_MODES (a retired one, a misspelling) is
    refused when the step is traced instead of silently running another
    algorithm."""
    from babble_tpu.ops.ingest import FD_MODES, ingest_impl, ingest_rounds_impl
    from babble_tpu.ops.state import DagConfig, init_state

    assert fd_mode not in FD_MODES
    dag = random_gossip_arrays(4, 40, seed=1)
    cfg = DagConfig(n=4, e_cap=40, s_cap=dag.max_chain + 1, r_cap=8)
    batch = batch_from_arrays(dag)
    with pytest.raises(ValueError, match="fd_mode"):
        ingest_impl(cfg, init_state(cfg), fd_mode, batch)
    with pytest.raises(ValueError, match="fd_mode"):
        ingest_rounds_impl(cfg, init_state(cfg), fd_mode, batch)


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
    """The C++ reference-algorithm baseline (the tests' reference) must agree
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
