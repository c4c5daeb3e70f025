"""Compiles for a described v5e chip, with no chip attached.

The TPU compiler is installed here and compiles for a topology it is
only told about (on-chip guide section 2), so these tests refuse, at no
chip time, what the chip's compiler would refuse: a kernel over its
fast-memory budget, a program over the 16 GB of HBM.  ``jax.default_backend``
still reports the CPU here, so the tests that must trace the chip's own
branches (select-accumulate median, int8 one-hot strongly-see) patch it
to ``"tpu"`` inside the test.  Nothing runs: a compile that passes is
not a chip run.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports this file.  Keep every chip compile in this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

#: one v5e chip's HBM (Google Cloud "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(monkeypatch):
    """No persistent cache around the compiles (a described-chip entry
    cannot be read back without a chip), and the chip's branches."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_la_walk_compiles_exactly_where_walk_supported(one_chip,
                                                       chip_compile):
    """The Pallas walk at the largest e_cap ``walk_supported`` admits at
    n = 64 compiles as the chip runs it (interpret=False); one event
    more, and at the 100k batch shape, the compiler refuses it (SMEM)
    and ``walk_supported`` says False."""
    from babble_tpu.ops.pallas_ingest import la_walk, walk_supported

    n, s_cap = 64, 1200
    lo, hi = 65_536, 200_000          # supported / unsupported
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if walk_supported(n, mid, s_cap) else (lo, mid)

    def compiles(e_cap: int) -> bool:
        idx = jax.ShapeDtypeStruct((e_cap + 1,), jnp.int32,
                                   sharding=one_chip)
        ne = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        try:
            la_walk.lower(e_cap, n, idx, idx, idx, idx, ne, False).compile()
        except Exception as e:     # the compiler's refusal
            assert "memory" in str(e), e
            return False
        return True

    for e_cap in (65_536, lo, hi, 100_000):
        assert compiles(e_cap) == walk_supported(n, e_cap, s_cap), e_cap
    assert walk_supported(n, lo, s_cap) and not walk_supported(n, hi, s_cap)


def test_fused_step_compiles_on_chip_branches(one_chip, chip_compile):
    """The bench headline / cli sim step at 1,024 x 100,000, traced with
    the chip's select-accumulate median, fits one chip."""
    from babble_tpu.cli import sim_step
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays

    dag = random_gossip_arrays(1024, 100_000, seed=7)
    cfg, step = sim_step(dag, 16)
    assert cfg.s_cap < 2048            # the select-accumulate branch
    compiled = step.lower(
        _abstract(jax.eval_shape(lambda: init_state(cfg)), one_chip),
        _abstract(batch_from_arrays(dag), one_chip),
    ).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_fused_step_carries_its_phase_scopes(one_chip, chip_compile):
    """The sim step compiled for the chip at a testnet-like 4 x 4,096
    keeps every phase scope in its instructions' ``op_name`` metadata,
    where a profile's operations are joined with their phase."""
    import re

    from babble_tpu.cli import sim_step
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays

    dag = random_gossip_arrays(4, 4096, seed=7)
    cfg, step = sim_step(dag, 512)
    text = step.lower(
        _abstract(jax.eval_shape(lambda: init_state(cfg)), one_chip),
        _abstract(batch_from_arrays(dag), one_chip),
    ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for path in ("babble_ingest/la/", "babble_ingest/fd/",
                 "babble_ingest/rounds/", "babble_fame/", "babble_order/"):
        assert any(path in n for n in names), path


def test_fork_pipeline_carries_its_phase_scopes(one_chip, chip_compile):
    """The sim step on a 4 x 4,096 DAG with one equivocation (the fork
    pipeline) compiled for the chip keeps the fused step's six scope
    paths: ``babble_ingest`` own work (fork detection) and its ``la`` /
    ``fd`` / ``rounds`` children, ``babble_fame``, ``babble_order``."""
    import re

    from babble_tpu.cli import sim_inputs, sim_step
    from babble_tpu.ops.forks import ForkConfig
    from babble_tpu.sim.arrays import ArrayDag
    from benchmark.reference import fork_native

    dag = fork_native.fork_dag(4, 4096, 7, 1)
    adag = ArrayDag(4, *(dag[k] for k in ("sp", "op", "creator", "seq",
                                          "ts", "mbit", "levels")), 7)
    cfg, step = sim_step(adag, 512)
    assert isinstance(cfg, ForkConfig)
    text = step.lower(
        *_abstract(sim_inputs(adag, cfg, 2560), one_chip)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for path in ("babble_ingest/la/", "babble_ingest/fd/",
                 "babble_ingest/rounds/", "babble_fame/", "babble_order/"):
        assert any(path in n for n in names), path
    assert any(re.search(r"babble_ingest/(?!la/|fd/|rounds/)", n)
               for n in names), "babble_ingest's own work"


def test_wide_onehot_strongly_see_compiles_at_10k(one_chip, chip_compile):
    """The wide engine's per-block strongly-see partial at n = 10,000
    takes the int8 one-hot matmul on the chip, and compiles there."""
    from babble_tpu.ops.state import DagConfig
    from babble_tpu.ops.wide import (
        _block_width, _jits, _use_onehot_partial, block_count,
    )

    cfg = DagConfig(n=10_000, e_cap=620_000, s_cap=110, r_cap=16,
                    coord8=True)
    assert _use_onehot_partial(cfg)
    C = block_count(cfg)
    rows = jax.ShapeDtypeStruct((cfg.n, _block_width(cfg, C)),
                                cfg.coord_dtype, sharding=one_chip)
    acc = jax.ShapeDtypeStruct((cfg.n, cfg.n), jnp.int32,
                               sharding=one_chip)
    compiled = _jits(cfg, C)["ss_partial"].lower(rows, rows, acc).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_sharded_stream_tally_fits_each_chip_at_10k(topo, chip_compile):
    """The 10k stream's strongly-see tally over the blocks as the stream
    lays them out on ``make_mesh(4)`` (2x2): spread over all four chips
    it fits each one; over "p" alone, a copy per "ev" row, it did not
    (18.33 GB, PR 21)."""
    from babble_tpu.ops.state import DagConfig
    from babble_tpu.ops.wide import (
        _block_width, _jits, block_count, block_sharding,
    )
    from babble_tpu.parallel import make_mesh

    cfg = DagConfig(n=10_000, e_cap=660_000, s_cap=110, r_cap=16,
                    coord8=True)
    mesh = make_mesh(4, devices=topo.devices)
    assert dict(mesh.shape) == {"ev": 2, "p": 2}
    C = -(-block_count(cfg) // mesh.size) * mesh.size
    rows = jax.ShapeDtypeStruct((C, cfg.n, _block_width(cfg, C)),
                                cfg.coord_dtype,
                                sharding=block_sharding(mesh))
    compiled = _jits(cfg, C)["ss_stacked"].lower(rows, rows).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_fork_pipeline_fits_one_chip(one_chip, chip_compile):
    """The byzantine batch pipeline at 1,024 x 100,000 (the shape whose
    CPU run asked for 1.14 TB) fits one chip's 16 GB."""
    from babble_tpu.ops.forks import fork_pipeline_impl
    from babble_tpu.sim.arrays import random_byzantine_fork_batch

    cfg, batch = random_byzantine_fork_batch(1024, 100_000, seed=11,
                                             fork_rate=0.02, r_cap=16)
    compiled = jax.jit(functools.partial(fork_pipeline_impl, cfg)).lower(
        _abstract(batch, one_chip)).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


#: 1% of one chip's HBM: the most either first-descendant fill may keep
#: as temporaries at the benchmark cells' shape
FD_TEMP_BYTES = V5E_HBM_BYTES // 100


def _abstract_fork_batch(cfg, sched_rows, sharding):
    """A ForkBatch of abstract arrays at ``cfg``'s capacities (dtypes
    from a tiny random batch), with a schedule of ``sched_rows`` rows."""
    from babble_tpu.ops import forks as F
    from babble_tpu.sim.arrays import random_byzantine_fork_batch

    _, tiny = random_byzantine_fork_batch(4, 64, seed=1, r_cap=16)
    e1, b, s1 = cfg.e_cap + 1, cfg.b, cfg.s_cap + 1
    shapes = dict(sp=(e1,), op=(e1,), ebr=(e1,), eseq=(e1,), ecr=(e1,),
                  ts=(e1,), mbit=(e1,), sched=(sched_rows, b), cp=(b, b),
                  ce=(b, s1), cnt=(b,), owner=(b, s1), n_events=(),
                  rseed=(e1,), wseed=(e1,), s_off=(b,))
    return F.ForkBatch(**{
        f: jax.ShapeDtypeStruct(shapes[f], getattr(tiny, f).dtype,
                                sharding=sharding)
        for f in F.ForkBatch._fields})


def _fork_fd_chains_temp(cfg, sched_rows, sharding):
    from babble_tpu.ops import forks as F

    batch = _abstract_fork_batch(cfg, sched_rows, sharding)
    la = jax.ShapeDtypeStruct((cfg.e_cap + 1, cfg.b), jnp.int32,
                              sharding=sharding)
    lowered = jax.jit(lambda bt, l: F._fd_chains(cfg, bt, l)).lower(
        batch, la)
    return lowered.compile().memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("fill", ["fork", "honest"])
def test_fd_fill_temporaries_at_the_cells_shape(one_chip, chip_compile,
                                                fill):
    """Both first-descendant fills at the benchmark cells' shape (4 x
    65,536 events, chains of 16,386 positions; the fork cell's k = 2)
    keep under 1% of the chip as temporaries: the chain-view search's
    chunks stay bounded."""
    from babble_tpu.ops import forks as F
    from babble_tpu.ops.ingest import _fd_full
    from babble_tpu.ops.state import DagConfig, init_state

    e_cap, s_cap = 65_536, 16_385
    if fill == "fork":
        cfg = F.ForkConfig(n=4, k=2, e_cap=e_cap, s_cap=s_cap, r_cap=4224)
        temp = _fork_fd_chains_temp(cfg, 38_912, one_chip)
    else:
        cfg = DagConfig(n=4, e_cap=e_cap, s_cap=s_cap, r_cap=4224)
        state = _abstract(jax.eval_shape(lambda: init_state(cfg)), one_chip)
        lowered = jax.jit(lambda st: _fd_full(st, cfg)).lower(state)
        temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < FD_TEMP_BYTES, temp


def test_fork_fd_chains_fits_wide_and_deep(one_chip, chip_compile):
    """The fork pipeline's chain-view fill where the cost model sends a
    wide DAG to it (1,024 validators x 2 branch slots, 131,072 events,
    a schedule just deep enough) keeps under a quarter of the chip as
    temporaries: its chain axis runs in chunks.  The whole [B, S+1, B]
    view at once kept 17.2 GB."""
    from babble_tpu.ops import forks as F
    from babble_tpu.ops.state import fd_reverse_scan_wins

    cfg = F.ForkConfig(n=1024, k=2, e_cap=131_072, s_cap=256, r_cap=16)
    rows = int((cfg.k * cfg.e_cap) ** 2 * 4.8e-7) + 1
    assert not fd_reverse_scan_wins(rows, cfg.e_cap, cfg.k)
    temp = _fork_fd_chains_temp(cfg, rows, one_chip)
    assert temp < V5E_HBM_BYTES // 4, temp
