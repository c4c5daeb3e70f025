"""Array-native DAG generation: the zero-object simulation path.

At the BASELINE north-star sizes (1M events) the Python Event object path
(msgpack + SHA-256 + dict indexing per event, sim/generator.py) costs more
than the device pipeline it feeds.  This module produces the dense
struct-of-arrays form directly — the exact fields ops.ingest.EventBatch
wants — via the native C++ graph builder (babble_tpu.native) with a
bit-identical numpy/Python fallback.

The gossip shape matches sim/generator.py and the reference's live loop
(node/node.go:193-222): each step one receiver syncs from one random
sender, minting an event with parents (own head, sender head).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

from .. import native
from ..membership.quorum import supermajority

_BASE_TS = 1_700_000_000_000_000_000
_MASK64 = (1 << 64) - 1


@dataclass
class ArrayDag:
    """Struct-of-arrays DAG; slot == generation order == topological."""

    n: int
    sp: np.ndarray        # i32[E] self-parent slot, -1 for roots
    op: np.ndarray        # i32[E] other-parent slot, -1 for roots
    creator: np.ndarray   # i32[E]
    seq: np.ndarray       # i32[E]
    ts: np.ndarray        # i64[E]
    mbit: np.ndarray      # bool[E]
    levels: np.ndarray    # i32[E]
    seed: int

    @property
    def n_events(self) -> int:
        return len(self.sp)

    @property
    def n_levels(self) -> int:
        return int(self.levels.max()) + 1 if len(self.levels) else 0

    @property
    def max_chain(self) -> int:
        return int(self.seq.max()) + 1 if len(self.seq) else 0

    @property
    def branch_slots(self) -> int:
        """Branch columns the DAG needs per creator: the most chain tips
        (events no other event extends) that any creator has.  Above 1,
        the DAG holds an equivocation: two events of one creator at one
        index."""
        if not self.n_events:
            return 1
        extended = np.zeros(self.n_events, bool)
        extended[self.sp[self.sp >= 0]] = True
        return int(np.bincount(self.creator[~extended],
                               minlength=self.n).max())

    def participants(self) -> Dict[str, int]:
        """Fake identities compatible with sim.generator's naming."""
        from .generator import _fake_pub

        return {
            ("0x" + _fake_pub(i).hex().upper()): i for i in range(self.n)
        }


def _splitmix64_py(state: int) -> Tuple[int, int]:
    state = (state + 0x9E3779B97F4A7C15) & _MASK64
    z = state
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK64
    return state, z ^ (z >> 31)


def _gossip_dag_py(
    seed: int, n: int, n_events: int, ts_granularity_ns: int, base_ts: int
) -> ArrayDag:
    """Pure-Python twin of native gossip_dag (bit-identical output)."""
    sp = np.full(n_events, -1, np.int32)
    op = np.full(n_events, -1, np.int32)
    creator = np.zeros(n_events, np.int32)
    seq = np.zeros(n_events, np.int32)
    ts = np.full(n_events, base_ts, np.int64)
    mbit = np.zeros(n_events, bool)
    levels = np.zeros(n_events, np.int32)

    st = (seed * 2 + 1) & _MASK64
    heads = [0] * n
    seqs = [1] * n
    k = 0
    for i in range(min(n, n_events)):
        creator[k] = i
        st, z = _splitmix64_py(st)
        mbit[k] = bool(z & 1)
        heads[i] = k
        k += 1

    t = 0
    while k < n_events:
        t += 1
        st, z = _splitmix64_py(st)
        r = int(z % n)
        st, z = _splitmix64_py(st)
        s = int(z % (n - 1))
        if s >= r:
            s += 1
        raw = t * 1_987_963
        ts[k] = base_ts + (raw // ts_granularity_ns) * ts_granularity_ns
        sps, opsl = heads[r], heads[s]
        sp[k], op[k] = sps, opsl
        creator[k] = r
        seq[k] = seqs[r]
        seqs[r] += 1
        levels[k] = 1 + max(int(levels[sps]), int(levels[opsl]))
        st, z = _splitmix64_py(st)
        mbit[k] = bool(z & 1)
        heads[r] = k
        k += 1

    return ArrayDag(n, sp, op, creator, seq, ts, mbit, levels, seed)


def random_gossip_arrays(
    n: int,
    n_events: int,
    seed: int = 0,
    ts_granularity_ns: int = 1_000,
    base_ts: int = _BASE_TS,
    force_python: bool = False,
) -> ArrayDag:
    """Generate a gossip DAG as dense arrays (native C++ when available)."""
    lib = None if force_python else native.load()
    if lib is None:
        return _gossip_dag_py(seed, n, n_events, ts_granularity_ns, base_ts)

    import ctypes

    sp = np.empty(n_events, np.int32)
    op = np.empty(n_events, np.int32)
    creator = np.empty(n_events, np.int32)
    seq = np.empty(n_events, np.int32)
    ts = np.empty(n_events, np.int64)
    mbit = np.empty(n_events, np.uint8)
    levels = np.empty(n_events, np.int32)
    heads = np.empty(n, np.int32)

    def p(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    lib.gossip_dag(
        ctypes.c_uint64(seed), n, n_events,
        ts_granularity_ns, base_ts,
        p(sp, ctypes.c_int32), p(op, ctypes.c_int32),
        p(creator, ctypes.c_int32), p(seq, ctypes.c_int32),
        p(ts, ctypes.c_int64), p(mbit, ctypes.c_uint8),
        p(levels, ctypes.c_int32), p(heads, ctypes.c_int32),
    )
    return ArrayDag(
        n, sp, op, creator, seq, ts, mbit.astype(bool), levels, seed
    )


def build_schedule(levels: np.ndarray, n_levels: int = 0) -> np.ndarray:
    """Group indices by level into an i32[T, B] table, -1 padded (the
    ops.ingest schedule).  Native when available, numpy otherwise."""
    k = len(levels)
    if k == 0:
        return np.full((1, 1), -1, np.int32)
    if not n_levels:
        n_levels = int(levels.max()) + 1
    lib = native.load()
    if lib is not None:
        import ctypes

        counts = np.empty(n_levels, np.int32)
        lv = np.ascontiguousarray(levels, np.int32)
        width = int(lib.max_level_width(
            lv.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), k, n_levels,
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ))
        sched = np.empty((n_levels, width), np.int32)
        fill = np.empty(n_levels, np.int32)
        rc = lib.build_schedule(
            lv.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), k, n_levels,
            width,
            sched.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            fill.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc == 0:
            return sched
        # fall through to numpy on the (impossible) width mismatch

    order = np.argsort(levels, kind="stable")
    sorted_lv = levels[order]
    ulev, starts, counts = np.unique(
        sorted_lv, return_index=True, return_counts=True
    )
    width = int(counts.max())
    sched = np.full((n_levels, width), -1, np.int32)
    cols = np.arange(k) - starts[np.searchsorted(ulev, sorted_lv)]
    sched[sorted_lv, cols] = order.astype(np.int32)
    return sched


def events_from_arrays(dag: ArrayDag):
    """Materialize Event objects from an ArrayDag (engine interop / tests).
    Pseudo-signatures derive from the slot so hashes are deterministic."""
    from ..core.event import Event, EventBody
    from .generator import _fake_pub

    pubs = [_fake_pub(i) for i in range(dag.n)]
    events = []
    hexes = []
    for k in range(dag.n_events):
        body = EventBody(
            transactions=[],
            self_parent=hexes[dag.sp[k]] if dag.sp[k] >= 0 else "",
            other_parent=hexes[dag.op[k]] if dag.op[k] >= 0 else "",
            creator=pubs[dag.creator[k]],
            timestamp=int(dag.ts[k]),
            index=int(dag.seq[k]),
        )
        ev = Event(body=body, r=(k << 1) | 1, s=(k << 2) | 1)
        events.append(ev)
        hexes.append(ev.hex())
    return events


def batch_from_arrays(dag: ArrayDag, bucket=None):
    """ArrayDag -> ops.ingest.EventBatch (single full-DAG batch)."""
    import jax.numpy as jnp

    from ..ops.ingest import EventBatch

    k = dag.n_events
    kpad = bucket(k) if bucket else k
    sched = build_schedule(dag.levels)

    def pad1(a, fill, dtype):
        out = np.full(kpad, fill, dtype)
        out[:k] = a
        return out

    return EventBatch(
        sp=jnp.asarray(pad1(dag.sp, -1, np.int32)),
        op=jnp.asarray(pad1(dag.op, -1, np.int32)),
        creator=jnp.asarray(pad1(dag.creator, 0, np.int32)),
        seq=jnp.asarray(pad1(dag.seq, 0, np.int32)),
        ts=jnp.asarray(pad1(dag.ts, 0, np.int64)),
        mbit=jnp.asarray(pad1(dag.mbit, False, bool)),
        k=jnp.asarray(k, jnp.int32),
        sched=jnp.asarray(sched),
    )


def pad_schedule(sched: np.ndarray, rows: int, width: int) -> np.ndarray:
    """A level schedule padded with -1 to ``rows`` x ``width``, so that
    DAGs of equal sizes run one compiled program; ``rows`` 0 leaves it
    as it is."""
    if not rows:
        return sched
    if sched.shape[0] > rows or sched.shape[1] > width:
        raise ValueError(f"the schedule {sched.shape} exceeds "
                         f"{rows} x {width}")
    out = np.full((rows, width), -1, np.int32)
    out[:sched.shape[0], :sched.shape[1]] = sched
    return out


def fork_batch_from_arrays(dag: ArrayDag, cfg, sched_rows: int = 0):
    """ArrayDag -> ops.forks.ForkBatch, its events placed in branch
    columns by ForkDag's rules (ops.forks.ForkArrays); a ``sched_rows``
    pads the level schedule to that many rows of ``cfg.b`` (a level
    holds at most one event per branch column)."""
    from ..ops.forks import ForkArrays

    lay = ForkArrays(dag.n, cfg.k, dag.sp, dag.op, dag.creator, dag.seq,
                     dag.ts)
    sched = build_schedule(np.asarray(lay.levels, np.int32))
    return lay.build_batch(cfg, dag.mbit,
                           pad_schedule(sched, sched_rows, cfg.b))


def cap_schedule_width(sched: np.ndarray, max_width: int) -> np.ndarray:
    """Split wide schedule rows into several rows of <= max_width entries.

    Any partition of a topological level is still a valid schedule (its
    members are mutually non-ancestral, and splitting preserves order), so
    this only bounds the per-step working set — the fork kernels gather
    [row_width, B, B] witness tensors per step, which must not scale with
    the DAG's level width."""
    t, w = sched.shape
    if w <= max_width:
        return sched
    parts = -(-w // max_width)
    out = np.full((t * parts, max_width), -1, np.int32)
    for r in range(t):
        row = sched[r][sched[r] >= 0]
        for p in range(-(-max(len(row), 1) // max_width)):
            chunk = row[p * max_width : (p + 1) * max_width]
            out[r * parts + p, : len(chunk)] = chunk
    keep = (out >= 0).any(axis=1)
    keep[0] = True
    return out[keep]


def random_byzantine_fork_batch(
    n: int,
    n_events: int,
    byz_frac: float = 1 / 3,
    fork_rate: float = 0.05,
    seed: int = 0,
    ts_granularity_ns: int = 1_000,
    base_ts: int = _BASE_TS,
    sched_width: int = 32,
    r_cap: int = 0,
):
    """Zero-object byzantine DAG: gossip arrays where (up to the BFT
    bound) 1/3 of creators equivocate exactly once, emitted directly as
    the (ForkConfig, ForkBatch) the fork pipeline consumes — the
    1024-node byzantine BASELINE config at bench scale, where the Python
    Event-object path would dominate the measurement.

    One fork per byzantine creator (branch budget K=2); matches
    sim.generator.random_byzantine_dag's shape with forks_per_node=1."""
    from ..ops.forks import ForkArrays, ForkConfig

    rng = np.random.default_rng(seed)
    k = 2
    n_byz = min(int(byz_frac * n), n - supermajority(n))

    sp = np.full(n_events, -1, np.int32)
    op = np.full(n_events, -1, np.int32)
    eseq = np.zeros(n_events, np.int32)
    ecr = np.zeros(n_events, np.int32)
    ts = np.zeros(n_events, np.int64)
    mbit = rng.integers(0, 2, n_events).astype(bool)
    levels = np.zeros(n_events, np.int32)

    heads = np.full(n, -1, np.int32)          # current head slot per node
    cur_idx = np.full(n, -1, np.int32)
    forked = np.zeros(n, bool)
    own_slots: list = [[] for _ in range(n)]  # all own slots in order

    e = 0
    for i in range(min(n, n_events)):
        ecr[e] = i
        ts[e] = base_ts
        heads[i] = e
        cur_idx[i] = 0
        own_slots[i].append(e)
        e += 1

    t = 0
    while e < n_events:
        t += 1
        r = int(rng.integers(0, n))
        s = int(rng.integers(0, n - 1))
        if s >= r:
            s += 1
        raw = t * 1_987_963
        tstamp = base_ts + (raw // ts_granularity_ns) * ts_granularity_ns

        sp_slot = heads[r]
        idx = cur_idx[r] + 1
        if (r < n_byz and not forked[r] and cur_idx[r] >= 1
                and rng.random() < fork_rate):
            # equivocate once: branch off a random earlier own event
            # (ForkDag's rules give it, and what extends it, column r*k+1)
            j = int(rng.integers(0, len(own_slots[r]) - 1))
            sp_slot = own_slots[r][j]
            idx = eseq[sp_slot] + 1
            forked[r] = True
        sp[e] = sp_slot
        op[e] = heads[s]
        eseq[e] = idx
        ecr[e] = r
        ts[e] = tstamp
        levels[e] = 1 + max(levels[sp_slot], levels[heads[s]])
        heads[r] = e
        cur_idx[r] = idx
        own_slots[r].append(e)
        e += 1

    max_chain = int(eseq.max()) + 1
    # fame tensors are [R, B, B]: keep r_cap tight (callers size it to the
    # expected round count; the bench asserts post-run headroom)
    cfg = ForkConfig(
        n=n, k=k,
        e_cap=1 << (n_events - 1).bit_length(),
        s_cap=1 << max(3, (max_chain + 1 - 1).bit_length()),
        r_cap=r_cap or 1 << max(
            3, (int(levels.max()) // 3 + 4 - 1).bit_length()
        ),
    )
    lay = ForkArrays(n, k, sp, op, ecr, eseq, ts)
    sched = cap_schedule_width(build_schedule(levels), sched_width)
    return cfg, lay.build_batch(cfg, mbit, sched)
