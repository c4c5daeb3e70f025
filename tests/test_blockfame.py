"""Blockwise DecideFame + blocked strongly-see primitives (ops/ss.py).

The 10k-participant north-star config cannot materialize the diagonal
fame scan's [R, N, N] witness tensors (VERDICT r2 missing #1); these
tests pin the blockwise replacements to the originals bit-for-bit:

- ss_counts_onehot (int8 MXU formulation) == ss_counts_compare on
  adversarial value patterns (sentinels, INF, out-of-band),
- decide_fame_block_impl == decide_fame_impl across random gossip DAGs
  (consensus-observable parity, including lcr), and the diagonal scan's
  early exit stops within a few steps of a DAG's hundreds of rounds,
- the chunked decide_order median path == the unchunked one.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from babble_tpu.ops import ingest as ingest_ops
from babble_tpu.ops.fame import (
    decide_fame_block_impl,
    decide_fame_impl,
    diagonal_vote_scan,
    fame_mode,
)
from babble_tpu.ops.order import decide_order_impl
from babble_tpu.ops.ss import ss_counts_compare, ss_counts_onehot
from babble_tpu.ops.state import (
    INT32_MAX,
    DagConfig,
    assert_consensus_parity,
    init_state,
)
from babble_tpu.sim.arrays import (
    ArrayDag,
    batch_from_arrays,
    random_gossip_arrays,
)


def _ref_counts(la, fd):
    return (la[:, None, :] >= fd[None, :, :]).sum(-1).astype(np.int32)


@pytest.mark.parametrize("shape", [(7, 5, 9), (64, 64, 33), (130, 70, 257)])
def test_ss_counts_formulations_agree(shape):
    a, b, k = shape
    s_hi = 13
    rng = np.random.default_rng(a * 1000 + k)
    la = rng.integers(-1, s_hi + 1, (a, k)).astype(np.int32)
    fd = rng.integers(0, s_hi + 2, (b, k)).astype(np.int32)
    # sprinkle INF ("no first descendant") entries
    fd = np.where(rng.random((b, k)) < 0.15, INT32_MAX, fd)
    ref = _ref_counts(la, fd)
    got_c = np.asarray(ss_counts_compare(jnp.asarray(la), jnp.asarray(fd),
                                         a_chunk=32))
    got_o = np.asarray(ss_counts_onehot(jnp.asarray(la), jnp.asarray(fd),
                                        s_hi, k_chunk_elems=1 << 9))
    np.testing.assert_array_equal(got_c, ref)
    np.testing.assert_array_equal(got_o, ref)


def test_ss_counts_onehot_range_compression():
    """With per-chain offsets, values far outside [0, s_hi] stay exact as
    long as the *spread* fits the band."""
    rng = np.random.default_rng(0)
    a = b = 40
    k = 25
    base = rng.integers(0, 1000, (k,)).astype(np.int32)
    la = (base[None, :] + rng.integers(-1, 8, (a, k))).astype(np.int32)
    fd = (base[None, :] + rng.integers(0, 8, (b, k))).astype(np.int32)
    fd = np.where(rng.random((b, k)) < 0.2, INT32_MAX, fd)
    ref = _ref_counts(la, fd)
    got = np.asarray(
        ss_counts_onehot(jnp.asarray(la), jnp.asarray(fd), 8,
                         off=jnp.asarray(base))
    )
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize(
    "n,e,r_cap,seed",
    [(8, 200, 32, 1), (16, 500, 32, 2), (32, 2000, 64, 3), (5, 60, 16, 5)],
)
def test_blockwise_fame_parity(n, e, r_cap, seed):
    dag = random_gossip_arrays(n, e, seed=seed)
    batch = batch_from_arrays(dag)
    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 2, r_cap=r_cap)

    def run(fame_fn):
        st = ingest_ops.ingest_impl(cfg, init_state(cfg), "fast", batch)
        st = fame_fn(cfg, st)
        st = decide_order_impl(cfg, st)
        return st

    ref = jax.jit(functools.partial(run, decide_fame_impl))()
    blk = jax.jit(functools.partial(run, decide_fame_block_impl))()
    assert_consensus_parity(ref, blk, e, label=f"blockfame n={n}")
    assert int(ref.lcr) >= 0 or e < 100  # the DAGs actually decide fame


# 4 validators x 2,048 events: some 150 rounds, so the scan's old bound
# max_round - lcr is ~150 steps; one compiled program for every seed
GOSSIP4 = DagConfig(n=4, e_cap=2048, s_cap=1024, r_cap=256)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fame_order(cfg, fame_fn, gate, st):
    return decide_order_impl(cfg, fame_fn(cfg, st, gate=gate))


_scan = jax.jit(diagonal_vote_scan, static_argnums=(0, 2))


@functools.lru_cache(maxsize=4)
def _gossip4_ingested(seed):
    batch = batch_from_arrays(random_gossip_arrays(4, GOSSIP4.e_cap, seed))
    return jax.jit(functools.partial(
        ingest_ops.ingest_impl, GOSSIP4, init_state(GOSSIP4), "fast"
    ))(batch)


@pytest.mark.parametrize("seed", [1, 2, 5, 6])
def test_diagonal_fame_early_exit_parity(seed):
    """The early-exit diagonal scan against the round-serial block form:
    same famous, lcr, rr and cts, in a few steps of a ~150-round DAG."""
    st = _gossip4_ingested(seed)
    diag = _fame_order(GOSSIP4, decide_fame_impl, False, st)
    blk = _fame_order(GOSSIP4, decide_fame_block_impl, False, st)
    assert_consensus_parity(blk, diag, GOSSIP4.e_cap,
                            label=f"early-exit fame seed={seed}")
    _, steps = _scan(GOSSIP4, st, False)
    assert int(st.max_round) >= 100
    assert int(diag.lcr) >= int(st.max_round) - 3
    assert 2 <= int(steps) <= 8


def test_diagonal_fame_exit_waits_through_coin_round():
    """Seed 5 has a round still open after the coin round (d = 4 with
    four validators): the scan must go on voting past it."""
    _, steps = _scan(GOSSIP4, _gossip4_ingested(5), False)
    assert int(steps) >= 5


def _dag_part(dag, lo, hi):
    """Events [lo, hi) of a DAG as one ingest batch (parent slots stay
    absolute, levels restart at 0)."""
    lv = dag.levels[lo:hi]
    return batch_from_arrays(ArrayDag(
        dag.n, dag.sp[lo:hi], dag.op[lo:hi], dag.creator[lo:hi],
        dag.seq[lo:hi], dag.ts[lo:hi], dag.mbit[lo:hi], lv - lv.min(),
        dag.seed,
    ))


def test_diagonal_fame_gated_second_batch():
    """Gated fame on a state with lcr >= 0 (the live engine's case): a
    second batch ingested after a first fame pass decides the same under
    the early-exit scan as under the block form."""
    dag = random_gossip_arrays(4, GOSSIP4.e_cap, seed=3)
    half = GOSSIP4.e_cap // 2
    ingest = jax.jit(functools.partial(
        ingest_ops.ingest_impl, GOSSIP4, fd_mode="incremental"
    ))
    block = functools.partial(decide_fame_block_impl, batch_window=False)
    outs = []
    for fame_fn in (decide_fame_impl, block):
        st = ingest(init_state(GOSSIP4), batch=_dag_part(dag, 0, half))
        st = _fame_order(GOSSIP4, fame_fn, True, st)
        lcr1 = int(st.lcr)
        assert lcr1 >= 0
        st = ingest(st, batch=_dag_part(dag, half, GOSSIP4.e_cap))
        st = _fame_order(GOSSIP4, fame_fn, True, st)
        assert int(st.lcr) > lcr1
        outs.append(st)
    assert_consensus_parity(outs[1], outs[0], GOSSIP4.e_cap,
                            label="gated early-exit fame, second batch")


def test_fame_mode_dispatch():
    assert fame_mode(DagConfig(n=1024, e_cap=100_000, s_cap=131,
                               r_cap=16)) == "diag"
    assert fame_mode(DagConfig(n=10_000, e_cap=100_000, s_cap=32,
                               r_cap=8)) == "block"


def test_blockwise_fame_sharded_parity(monkeypatch):
    """Force the block fame path under the 8-device ('ev','p') mesh and
    pin it to the single-device run bit-for-bit — the while_loop +
    dynamic-gather SPMD shape differs from the diag einsum the sharding
    annotations were written for, so the dispatch boundary needs its own
    mesh coverage."""
    import babble_tpu.ops.fame as fame_mod
    from babble_tpu.parallel import (
        make_mesh, make_sharded_step, pad_cfg_for_mesh, sharded_init_state,
    )
    from babble_tpu.parallel.sharded import consensus_step_impl

    monkeypatch.setattr(fame_mod, "BLOCK_FAME_THRESHOLD", 1)
    assert fame_mod.fame_mode(DagConfig(n=8, e_cap=100, s_cap=16,
                                        r_cap=8)) == "block"

    n, e = 16, 400
    dag = random_gossip_arrays(n, e, seed=11)
    batch = batch_from_arrays(dag)
    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 2, r_cap=32)
    mesh = make_mesh(8)
    cfg = pad_cfg_for_mesh(cfg, mesh)
    step = make_sharded_step(cfg, mesh, "full")
    sharded = step(sharded_init_state(cfg, mesh), batch)
    ref = jax.jit(functools.partial(consensus_step_impl, cfg, "full"))(
        init_state(cfg), batch
    )
    assert_consensus_parity(ref, sharded, int(ref.n_events),
                            label="sharded blockfame")
    assert int(ref.lcr) >= 0


def test_chunked_order_median_parity(monkeypatch):
    """Force the chunked median path at a small shape (with a ragged last
    chunk) and pin it to the full-tensor path's output."""
    import babble_tpu.ops.order as order_mod

    n, e = 16, 500
    dag = random_gossip_arrays(n, e, seed=9)
    batch = batch_from_arrays(dag)
    cfg = DagConfig(n=n, e_cap=e, s_cap=dag.max_chain + 2, r_cap=32)
    st = ingest_ops.ingest_impl(cfg, init_state(cfg), "fast", batch)
    st = decide_fame_impl(cfg, st)
    full = decide_order_impl(cfg, st)

    monkeypatch.setattr(order_mod, "MEDIAN_CHUNK_THRESHOLD", 1)
    monkeypatch.setattr(order_mod, "MEDIAN_CHUNK_ELEMS", 96 * n)  # ragged
    chunked = decide_order_impl(cfg, st)
    np.testing.assert_array_equal(np.asarray(full.cts)[:e],
                                  np.asarray(chunked.cts)[:e])
    np.testing.assert_array_equal(np.asarray(full.rr)[:e],
                                  np.asarray(chunked.rr)[:e])
    assert int((np.asarray(full.rr)[:e] >= 0).sum()) > 0
