"""Windowed wide-pipeline streaming (ops/stream.py): differential
bit-parity against the fused single-shot pipeline at small shapes with
forced blocking and forced compaction.

The stream sees the same DAG cut into mega-batches, evicts ordered
prefixes mid-run, and must produce the identical ordered set — same
round-received and same consensus timestamp per event — as the fused
pipeline that holds everything at once (the oracle-anchored reference
path, tests/test_wide.py)."""

import functools

import jax
import numpy as np
import pytest

from babble_tpu.ops.state import DagConfig, init_state
from babble_tpu.ops.stream import stream_consensus
from babble_tpu.parallel.sharded import consensus_step_impl
from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays


def _fused_reference(n, e, dag):
    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 3, r_cap=64)
    out = jax.jit(functools.partial(consensus_step_impl, cfg, "fast"))(
        init_state(cfg), batch_from_arrays(dag)
    )
    return cfg, out


def _assert_stream_matches(stream, out, e):
    rr_ref = np.asarray(out.rr)[:e]
    cts_ref = np.asarray(out.cts)[:e]
    ordered_ref = {
        int(s): (int(rr_ref[s]), int(cts_ref[s]))
        for s in np.nonzero(rr_ref >= 0)[0]
    }
    assert stream.ordered_total == len(ordered_ref), (
        f"ordered counts differ: stream {stream.ordered_total} vs fused "
        f"{len(ordered_ref)}"
    )
    assert stream.ordered == ordered_ref, "rr/cts diverged"
    assert stream.lcr == int(out.lcr)


@pytest.mark.parametrize("narrow", [{}, dict(coord8=True)])
def test_stream_parity_with_compaction(narrow):
    """~18 rounds of a 24-participant DAG streamed through a ~1.5-round
    window with aggressive eviction, forced 3-way blocking, int32 and
    int8 coordinates."""
    n, e = 24, 2800
    dag = random_gossip_arrays(n, e, seed=13)
    _, out = _fused_reference(n, e, dag)

    # residency is ~4.5 rounds (~150 events each at n=24) + one batch:
    # a 1400-row window streams the 2800-event DAG with several
    # compactions
    cfg = DagConfig(n=n, e_cap=1400, s_cap=110, r_cap=16, **narrow)
    logs = []
    stream = stream_consensus(
        cfg, dag, batch_events=350, n_blocks=3, round_margin=0,
        seq_window=16, compact_min=64, log=logs.append,
    )
    assert stream.evicted > 400, f"compaction never engaged: {logs}"
    assert stream.e_off == stream.evicted
    _assert_stream_matches(stream, out, e)


def test_stream_single_batch_equals_fresh_pipeline():
    """One mega-batch (no compaction) must match the one-shot wide
    pipeline bit-for-bit on the consensus surface."""
    from babble_tpu.ops.wide import run_wide_pipeline

    n, e = 24, 900
    dag = random_gossip_arrays(n, e, seed=5)
    _, out = _fused_reference(n, e, dag)

    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 3, r_cap=32)
    stream = stream_consensus(cfg, dag, batch_events=e, n_blocks=3,
                              compact_min=10**9)
    _assert_stream_matches(stream, out, e)

    wide = run_wide_pipeline(cfg, batch_from_arrays(dag), n_blocks=3)
    rr_w = np.asarray(wide.rr)[:e]
    rr_s = np.asarray(stream.state.rr)[:e]
    assert (rr_w == rr_s).all()


def test_stream_round_values_survive_window_roll():
    """Rounds of still-live events equal the fused reference's rounds
    for the same global slots even after several compactions (the
    frontier-finalize stale-round merge)."""
    n, e = 24, 2000
    dag = random_gossip_arrays(n, e, seed=21)
    _, out = _fused_reference(n, e, dag)
    rnd_ref = np.asarray(out.round)[:e]

    cfg = DagConfig(n=n, e_cap=1300, s_cap=110, r_cap=16)
    stream = stream_consensus(cfg, dag, batch_events=300, n_blocks=2,
                              seq_window=16, compact_min=64)
    assert stream.evicted > 0
    ne = stream.n_live
    rnd_live = np.asarray(stream.state.round[:ne])
    ref_live = rnd_ref[stream.e_off : stream.e_off + ne]
    assert (rnd_live == ref_live).all(), (
        f"{int((rnd_live != ref_live).sum())} live rounds diverged"
    )


def test_stream_rejects_window_overflow():
    n, e = 8, 400
    dag = random_gossip_arrays(n, e, seed=2)
    cfg = DagConfig(n=n, e_cap=128, s_cap=64, r_cap=16)
    with pytest.raises(ValueError, match="overflow|depth"):
        stream_consensus(cfg, dag, batch_events=200, compact_min=10**9)


def test_stream_stacked_sharded_parity():
    """VERDICT r4 item 3: the stacked block path (one vmapped program
    per phase instead of C host dispatches) and its p-sharded form over
    a real ("ev","p") mesh must stay bit-identical to the fused
    pipeline — the window x p-shards composition the v5e-8 north star
    needs.  The blocks ride mesh axis "p"; cross-block strongly-see /
    sees / median reductions become XLA collectives."""
    from babble_tpu.parallel.mesh import make_mesh

    n, e = 24, 2800
    dag = random_gossip_arrays(n, e, seed=13)
    _, out = _fused_reference(n, e, dag)
    cfg = DagConfig(n=n, e_cap=1400, s_cap=110, r_cap=16)

    stream = stream_consensus(cfg, dag, batch_events=350, n_blocks=4,
                              round_margin=0, seq_window=16,
                              compact_min=64, stacked=True)
    assert stream.evicted > 0, "compaction never engaged (stacked)"
    _assert_stream_matches(stream, out, e)

    mesh = make_mesh(8, shape=(1, 8))
    stream2 = stream_consensus(cfg, dag, batch_events=350, n_blocks=8,
                               round_margin=0, seq_window=16,
                               compact_min=64, mesh=mesh)
    assert stream2.evicted > 0, "compaction never engaged (sharded)"
    _assert_stream_matches(stream2, out, e)


def test_stream_spreads_blocks_over_every_mesh_device():
    """On the 2-D mesh ``make_mesh`` builds by default (here 4x2) the
    stream spreads its blocks over all 8 devices, one block each, not
    over "p" alone with a copy per "ev" row, and still matches the
    fused pipeline."""
    from babble_tpu.parallel.mesh import make_mesh

    n, e = 24, 2800
    dag = random_gossip_arrays(n, e, seed=13)
    _, out = _fused_reference(n, e, dag)
    cfg = DagConfig(n=n, e_cap=1400, s_cap=110, r_cap=16)
    mesh = make_mesh(8)
    assert mesh.shape["ev"] > 1
    stream = stream_consensus(cfg, dag, batch_events=350, round_margin=0,
                              seq_window=16, compact_min=64, mesh=mesh)
    shards = stream.la_blocks.addressable_shards
    assert stream.C == 8
    assert {s.device for s in shards} == set(mesh.devices.flat)
    assert all(s.data.shape[0] == 1 for s in shards)
    _assert_stream_matches(stream, out, e)
