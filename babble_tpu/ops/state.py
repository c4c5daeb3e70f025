"""Dense device-side DAG state: the struct-of-arrays hashgraph.

The reference keeps one Go struct per event with per-participant coordinate
slices (hashgraph/event.go:73-88) chased hash-by-hash through an LRU store.
Here the whole DAG lives in HBM as int32 tensors indexed by *slot* (insertion
order on this replica):

- ``la[E+1, N]``  last-ancestor seq per participant   (-1 = none)
- ``fd[E+1, N]``  first-descendant seq per participant (INT32_MAX = none)

Row ``E`` (the capacity row) is a sentinel: gathering a missing parent
(slot -1 is remapped to E) yields neutral values, which keeps every kernel
branch-free.  All consensus predicates are elementwise/reduction ops over
these two tensors (SURVEY.md §7 "key insight"):

    ancestor(x, y)      = la[x, creator[y]] >= seq[y]
    strongly_see(x, y)  = sum_k(la[x, k] >= fd[y, k]) >= 2N/3+1
    see(w, x)           = fd[x, creator[w]] <= seq[w]

Witness bookkeeping is creator-indexed: ``wslot[R+1, N]`` holds the slot of
creator j's witness in round r (honest DAGs have at most one; fork-aware
branches are a planned extension, SURVEY.md §5.2).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..membership.quorum import supermajority
from .pack import lane_count, pack_bits

I32 = jnp.int32
I64 = jnp.int64
INT32_MAX = np.int32(np.iinfo(np.int32).max)

# famous trilean encoding (reference roundInfo.go:24-30)
FAME_UNDEFINED = 0
FAME_TRUE = 1
FAME_FALSE = 2


class DagConfig(NamedTuple):
    """Static shape/threshold configuration (hashable; closed over by jit).

    ``n`` is the *array width* of the participant axis; when sharding pads
    that axis to the mesh (parallel/sharded.py), ``n_real`` holds the true
    participant count and thresholds (supermajority, coin-round period) use
    it.  Padded columns hold sentinel coordinates (la=-1, fd=inf) so
    they never contribute to any see/vote count.  n_real=0 means n is real.

    ``coord16`` stores the la/fd coordinate tensors as int16 instead of
    int32 — they are the dominant HBM residents ([E+1, N] each; 3.7 GB
    at 10k x 100k in i32), and every value is a per-creator seq, bounded
    by s_cap.  Halving them is what fits the deep 10k-participant
    configs on one 16 GB chip.  Requires s_cap < 16384 (headroom below
    the int16 INF sentinel); coord16_ok() checks.

    ``ts32`` narrows the ORDER phase's median working set (the i64
    ``tv`` tensor and its sort double, the HBM-bound tail of the
    94%-of-peak order kernel) to int32 by rebasing every timestamp
    against the minimum live timestamp inside the kernel.  Sorting is
    order-preserving under a constant shift, so medians are
    bit-identical to the i64 path whenever the live timestamp SPAN
    fits int32 (ts32_ok) — true for logical clocks (sim, chaos, bench
    streams), never for wall-clock ns fleets, which keep i64.  The
    engine enforces the span guard host-side before every flush.

    ``retired`` (membership plane) lists the participant columns of
    members that LEFT at an epoch boundary.  The column stays (removing
    it would renumber every other creator's coordinate column and
    scramble la/fd history); what changes is arithmetic: ``active_n``
    shrinks, so every supermajority threshold derived from this config
    tightens to the live set, the witness tables stop registering the
    retired creator's events (ops/ingest.py) and the finality gate
    stops waiting on its frozen chain (``head_round_min_math``).
    Retired columns contribute nothing to NEW quorum paths
    automatically: a strongly-see through creator c requires c to mint
    a descendant, which a departed member does not."""

    n: int          # participants (array width, possibly mesh-padded)
    e_cap: int      # event slot capacity
    s_cap: int      # per-creator sequence capacity
    r_cap: int      # round capacity
    n_real: int = 0
    coord16: bool = False
    coord8: bool = False     # overrides coord16 (shallowest chains only)
    ts32: bool = False       # i32 relative timestamps in the order median
    retired: Tuple[int, ...] = ()   # columns of departed members
    # kernel working-set diet (ROADMAP item 4): run the windowed fame
    # vote recursion and the order reception tallies over 8:1 bit-packed
    # uint8 lanes with popcount supermajorities instead of f32 einsum
    # tallies (ops/pack.py).  Counts are exact integers either way, so
    # the flag is bit-parity-preserving — it selects kernel math, not
    # semantics (differentially pinned in tests/test_diet.py).
    packed: bool = False

    @property
    def n_cols(self) -> int:
        """True participant-axis width (mesh padding excluded) — the
        column count retired members still occupy."""
        return self.n_real or self.n

    @property
    def active_n(self) -> int:
        return self.n_cols - len(self.retired)

    @property
    def super_majority(self) -> int:
        return supermajority(self.active_n)

    @property
    def lp(self) -> int:
        """uint8 lanes of the packed participant axis: ``ceil(n/8)``.
        Re-buckets when an epoch join widens the participant axis."""
        return lane_count(self.n)

    @property
    def coord_dtype(self):
        if self.coord8:
            return jnp.int8
        return jnp.int16 if self.coord16 else I32

    @property
    def fd_inf(self):
        """The 'no first descendant' sentinel, in coordinate dtype.
        Compare with >= (never ==): arithmetic on INF-holding tensors
        must stay on the safe side."""
        return np.asarray(np.iinfo(np.dtype(self.coord_dtype)).max,
                          np.dtype(self.coord_dtype))[()]


def config_from_fields(fields) -> DagConfig:
    """Rebuild a DagConfig from its serialized field list (checkpoint
    meta / AOT manifest).  msgpack/json round-trip the ``retired``
    tuple as a list — normalize it back or the config is unhashable
    and every jit closure over it fails."""
    cfg = DagConfig(*fields)
    if not isinstance(cfg.retired, tuple):
        cfg = cfg._replace(
            retired=tuple(int(c) for c in (cfg.retired or ()))
        )
    return cfg


def coord16_ok(s_cap: int) -> bool:
    """int16 coordinates are exact when every seq (plus slack for the
    +1-ish arithmetic in the kernels) stays clear of the INF sentinel."""
    return s_cap < (1 << 14)


def ts32_ok(ts_min: int, ts_max: int) -> bool:
    """int32 relative timestamps are exact when the live span (plus a
    little slack for the sentinel) stays clear of INT32_MAX."""
    return (ts_max - ts_min) < (1 << 31) - 4


def coord8_ok(s_cap: int) -> bool:
    """int8 coordinates: seqs (plus kernel slack) must stay below the
    int8 INF sentinel 127.  At 10k participants a 600k-event gossip DAG
    peaks near seq 90, so this covers the deep wide-bench configs —
    which is exactly where the coordinate tensors dominate HBM."""
    return s_cap < 120


class DagState(NamedTuple):
    """Device arrays.  Every per-event array has e_cap+1 rows; every
    per-round array has r_cap+1 rows; ce has an (n+1)-th dump row — the last
    row/col of each is the write-dump & gather-sentinel for padding.

    Rolling windows (bounded memory, reference caches.go:45-76 semantics):
    the three unbounded logical axes are windowed by traced offsets so a
    long-lived node's state stays a fixed shape with no recompilation:

    - event axis: device row i holds the event at *global* slot
      ``e_off + i``; ``compact`` shifts decided prefixes out.
    - seq axis: ``ce[c, q]`` holds creator c's event at *absolute* seq
      ``s_off[c] + q``.  Coordinate values in la/fd stay absolute seqs.
    - round axis: ``wslot/famous[r]`` describe *absolute* round
      ``r_off + r``.  ``round``/``rr``/``max_round``/``lcr`` stay absolute.

    Offsets are all zero until ``compact`` runs, so fresh/batch pipelines
    are unaffected."""

    # per-event
    sp: jnp.ndarray        # i32[E+1]   self-parent slot, -1 = none
    op: jnp.ndarray        # i32[E+1]   other-parent slot, -1 = none
    creator: jnp.ndarray   # i32[E+1]
    seq: jnp.ndarray       # i32[E+1]   index within creator chain; sentinel -1
    ts: jnp.ndarray        # i64[E+1]   claimed timestamp (ns)
    mbit: jnp.ndarray      # bool[E+1]  middle bit of identity hash (coin rounds)
    la: jnp.ndarray        # i32[E+1, N]
    fd: jnp.ndarray        # i32[E+1, N]
    round: jnp.ndarray     # i32[E+1]   sentinel/undefined -1
    witness: jnp.ndarray   # bool[E+1]
    rr: jnp.ndarray        # i32[E+1]   round received, -1 undecided
    cts: jnp.ndarray       # i64[E+1]   consensus timestamp

    # per-creator
    ce: jnp.ndarray        # i32[N+1, S+1]  (creator, seq) -> slot, -1
    cnt: jnp.ndarray       # i32[N+1]       events per creator (Known vector)

    # per-round (creator-indexed witnesses)
    wslot: jnp.ndarray     # i32[R+1, N]    witness slot, -1 = none
    famous: jnp.ndarray    # i8[R+1, N]     trilean
    # per-round supermajority threshold for round-increment evaluation
    # (membership plane): sm[r_loc] is the quorum an event whose max
    # parent round is r_off + r_loc must strongly-see among that
    # round's witnesses to increment.  Uniform (= cfg.super_majority)
    # until an epoch transition; across a boundary the old epoch's
    # rounds KEEP their old threshold so a straggler event inserted
    # after the transition is assigned the same round on every replica
    # regardless of which side of the apply it arrived on.  Row r_cap
    # is the backfill default compact() rolls in for fresh rounds.
    sm: jnp.ndarray        # i32[R+1]
    # packed per-round witness bitplanes (kernel working-set diet,
    # ROADMAP item 4): uint8 lanes along the participant axis, bit j of
    # lane l = creator 8l+j (ops/pack.py little-endian contract).  Both
    # are pure DERIVED caches of the wide tensors — recomputed by
    # repack_round_bits wherever wslot/famous/mbit change wholesale and
    # re-packed from the wide tensors at checkpoint restore — persisted
    # so the packed kernels read W-row lane slices instead of
    # re-gathering [W, N] event fields every flush.
    mbr: jnp.ndarray       # u8[R+1, LP] coin bits of each round's witnesses
    fmr: jnp.ndarray       # u8[R+1, LP] famous==TRUE bitmap per round

    # scalars
    n_events: jnp.ndarray  # i32  live (windowed) event count
    max_round: jnp.ndarray # i32  highest assigned round, -1 if none
    lcr: jnp.ndarray       # i32  last consensus round, -1 if none

    # rolling-window offsets (see class docstring)
    e_off: jnp.ndarray     # i32      global slot of device row 0
    s_off: jnp.ndarray     # i32[N+1] absolute seq of ce column 0, per creator
    r_off: jnp.ndarray     # i32      absolute round of wslot/famous row 0


#: Axis classification of every DagState field — the single source of
#: truth the device-plane lint rules consume (``bytes-model-coverage``):
#: the four tuples must PARTITION DagState._fields exactly, so a new
#: field fails lint until someone states which axis it grows along, and
#: every per-event/per-round tensor must then appear in the flush
#: traffic model (ops/flush.py FIELD_TRAFFIC) and the sharding specs
#: (parallel/sharded.py state_specs).  ``AXIS_CLASSIFIED_STATE`` names
#: the class the partition describes (this module also defines
#: DagConfig, which is plain static config, not device state).
AXIS_CLASSIFIED_STATE = "DagState"
PER_EVENT_FIELDS = ("sp", "op", "creator", "seq", "ts", "mbit",
                    "la", "fd", "round", "witness", "rr", "cts")
PER_ROUND_FIELDS = ("wslot", "famous", "sm", "mbr", "fmr")
PER_CREATOR_FIELDS = ("ce", "cnt", "s_off")
SCALAR_FIELDS = ("n_events", "max_round", "lcr", "e_off", "r_off")


def init_state(cfg: DagConfig,
               include_coords: bool = True) -> DagState:
    if cfg.coord8 and not coord8_ok(cfg.s_cap):
        raise ValueError(
            f"coord8 requires s_cap < 120 (got {cfg.s_cap}): int8 "
            "coordinates would wrap"
        )
    if cfg.coord16 and not cfg.coord8 and not coord16_ok(cfg.s_cap):
        raise ValueError(
            f"coord16 requires s_cap < 2^14 (got {cfg.s_cap}): int16 "
            "coordinates would wrap"
        )
    e1, n, s1, r1 = cfg.e_cap + 1, cfg.n, cfg.s_cap + 1, cfg.r_cap + 1
    return DagState(
        sp=jnp.full((e1,), -1, I32),
        op=jnp.full((e1,), -1, I32),
        creator=jnp.full((e1,), n, I32),       # sentinel creator = dump col
        seq=jnp.full((e1,), -1, I32),
        ts=jnp.zeros((e1,), I64),
        mbit=jnp.zeros((e1,), jnp.bool_),
        # include_coords=False: the blocked wide pipeline owns la/fd as
        # column blocks; allocating the fused twins here would double the
        # dominant residency before the blocks even exist
        la=jnp.full((e1, n), -1, cfg.coord_dtype)
        if include_coords else None,
        fd=jnp.full((e1, n), cfg.fd_inf, cfg.coord_dtype)
        if include_coords else None,
        round=jnp.full((e1,), -1, I32),
        witness=jnp.zeros((e1,), jnp.bool_),
        rr=jnp.full((e1,), -1, I32),
        cts=jnp.zeros((e1,), I64),
        ce=jnp.full((n + 1, s1), -1, I32),
        cnt=jnp.zeros((n + 1,), I32),
        wslot=jnp.full((r1, n), -1, I32),
        famous=jnp.zeros((r1, n), jnp.int8),
        sm=jnp.full((r1,), cfg.super_majority, I32),
        # packed bitplanes of an empty witness table are all-zero —
        # exactly what repack_round_bits computes over sentinel rows
        mbr=jnp.zeros((r1, cfg.lp), jnp.uint8),
        fmr=jnp.zeros((r1, cfg.lp), jnp.uint8),
        n_events=jnp.zeros((), I32),
        max_round=jnp.full((), -1, I32),
        lcr=jnp.full((), -1, I32),
        e_off=jnp.zeros((), I32),
        s_off=jnp.zeros((n + 1,), I32),
        r_off=jnp.zeros((), I32),
    )


def grow_state(state: DagState, old: DagConfig, new: DagConfig) -> DagState:
    """Copy arrays into larger-capacity buffers (sentinel rows preserved at
    the new last index).  Host-side, called rarely; triggers re-jit."""
    if old.coord_dtype != new.coord_dtype:
        raise ValueError(
            "cannot grow across coordinate dtypes: values would be "
            f"silently cast ({old.coord_dtype} -> {new.coord_dtype})"
        )
    fresh = init_state(new)

    def copy_events(dst, src):
        return dst.at[: old.e_cap].set(src[: old.e_cap])

    return fresh._replace(
        sp=copy_events(fresh.sp, state.sp),
        op=copy_events(fresh.op, state.op),
        creator=copy_events(fresh.creator, state.creator),
        seq=copy_events(fresh.seq, state.seq),
        ts=copy_events(fresh.ts, state.ts),
        mbit=copy_events(fresh.mbit, state.mbit),
        la=fresh.la.at[: old.e_cap].set(state.la[: old.e_cap]),
        fd=fresh.fd.at[: old.e_cap].set(state.fd[: old.e_cap]),
        round=copy_events(fresh.round, state.round),
        witness=copy_events(fresh.witness, state.witness),
        rr=copy_events(fresh.rr, state.rr),
        cts=copy_events(fresh.cts, state.cts),
        ce=fresh.ce.at[: old.n + 1, : old.s_cap].set(state.ce[:, : old.s_cap]),
        cnt=fresh.cnt.at[: old.n + 1].set(state.cnt),
        wslot=fresh.wslot.at[: old.r_cap].set(state.wslot[: old.r_cap]),
        famous=fresh.famous.at[: old.r_cap].set(state.famous[: old.r_cap]),
        sm=fresh.sm.at[: old.r_cap].set(state.sm[: old.r_cap]),
        mbr=fresh.mbr.at[: old.r_cap].set(state.mbr[: old.r_cap]),
        fmr=fresh.fmr.at[: old.r_cap].set(state.fmr[: old.r_cap]),
        n_events=state.n_events,
        max_round=state.max_round,
        lcr=state.lcr,
        e_off=state.e_off,
        s_off=fresh.s_off.at[: old.n + 1].set(state.s_off),
        r_off=state.r_off,
    )


def compact_impl(
    cfg: DagConfig,
    state: DagState,
    de: jnp.ndarray,        # i32: event slots to evict (a decided prefix)
    new_s_off: jnp.ndarray, # i32[N+1]: absolute seq of each creator's window start
    dr: jnp.ndarray,        # i32: rounds to roll off the witness tables
) -> DagState:
    """Roll the windows: shift every axis down in place (fixed shapes, no
    recompilation) — the device half of the reference's rolling caches
    (caches.go:45-76).  The caller (engine.maybe_compact) guarantees the
    evicted prefix is never referenced again: every evicted event is
    committed, below every creator's seq window, and of a round below the
    new r_off; chain slots ascend with seq, so kept seqs ↔ kept slots.

    Shift trick: row e_cap of every per-event array holds the same values
    as an untouched (init) row, so ``a[min(arange + de, e_cap)]`` both
    shifts the live rows down and back-fills the tail with fresh init/
    sentinel rows in one gather."""
    e1, s1, r1 = cfg.e_cap + 1, cfg.s_cap + 1, cfg.r_cap + 1

    eidx = jnp.minimum(jnp.arange(e1) + de, cfg.e_cap)
    remap = lambda v: jnp.where(v >= de, v - de, -1)  # slot values -> local

    # ce: per-creator column shift by (new_s_off - s_off), values remapped
    ds = (new_s_off - state.s_off)[:, None]                       # [N+1, 1]
    scol = jnp.minimum(jnp.arange(s1)[None, :] + ds, cfg.s_cap)
    ce = remap(jnp.take_along_axis(state.ce, scol, axis=1))

    ridx = jnp.minimum(jnp.arange(r1) + dr, cfg.r_cap)

    return state._replace(
        sp=remap(state.sp[eidx]),
        op=remap(state.op[eidx]),
        creator=state.creator[eidx],
        seq=state.seq[eidx],
        ts=state.ts[eidx],
        mbit=state.mbit[eidx],
        # blocked wide states own la/fd as column blocks (ops/wide.py
        # compact_block rolls those); here they are absent
        la=state.la[eidx] if state.la is not None else None,
        fd=state.fd[eidx] if state.fd is not None else None,
        round=state.round[eidx],
        witness=state.witness[eidx],
        rr=state.rr[eidx],
        cts=state.cts[eidx],
        ce=ce,
        wslot=remap(state.wslot[ridx]),
        famous=state.famous[ridx],
        # fresh rounds inherit the CURRENT epoch's threshold from the
        # sentinel row; rolled-off old-epoch rows are decided history
        sm=state.sm[ridx],
        # packed bitplanes roll with their rounds: surviving rows keep
        # witnesses whose slots survive (rounds below new r_off are the
        # only ones holding evicted slots), and the all-zero sentinel
        # row backfills fresh rounds like every other per-round table
        mbr=state.mbr[ridx],
        fmr=state.fmr[ridx],
        n_events=state.n_events - de,
        e_off=state.e_off + de,
        s_off=new_s_off,
        r_off=state.r_off + dr,
    )


compact = jax.jit(compact_impl, static_argnums=(0,), donate_argnums=(1,))


#: staleness horizon (rounds) for the live finality gate: a chain whose
#: head is this many rounds behind max_round stops blocking decisions.
#: Sound under partial synchrony: a chain that falls K rounds behind and
#: later catches up never produces witnesses for the skipped rounds (its
#: next event's round jumps to ~max_round via the fresh other-parent),
#: so the only divergence risk the horizon admits is a witness already
#: IN FLIGHT for K+ rounds of fleet progress — the explicit propagation
#: assumption that replaces the pre-PR implicit one of zero rounds.
HEAD_GATE_HORIZON = 8


def head_round_min_math(cfg: DagConfig, state: DagState) -> jnp.ndarray:
    """Effective head-round minimum for the live witness-set finality
    gate: the smallest chain-head round over minted, NON-STALE chains
    (-1 while any live-ish participant has never minted).

    Rounds are monotone along a chain and a round-r witness is the
    FIRST chain event of round r, so round i's witness set is final
    once every chain's head round has reached i — the gate the wide
    pipeline decides behind (ops/wide.py _head_round_min).  Ported
    verbatim that gate has all-N liveness: one crashed or partitioned
    peer freezes commitment (and therefore eviction and fast-forward
    recovery) fleet-wide forever.  The live twin adds the
    HEAD_GATE_HORIZON: a chain more than K rounds behind max_round is
    excluded from the minimum, so the fleet resumes committing K
    rounds after a peer goes dark, while the slow-but-live peers the
    gate exists for (chaos slow-peer: delays of a round or two) keep
    blocking decisions exactly as the strict gate would.

    Retired columns (membership plane) are excluded outright: a
    departed member's chain head is frozen forever, and without the
    mask every leave would stall commits for HEAD_GATE_HORIZON rounds
    before the staleness cutoff caught up."""
    n = cfg.n_cols
    cnt_w = state.cnt[:n] - state.s_off[:n]
    heads = state.ce[jnp.arange(n), jnp.clip(cnt_w - 1, 0, cfg.s_cap)]
    hr = state.round[sanitize(jnp.where(cnt_w > 0, heads, -1), cfg.e_cap)]
    hr = jnp.where(state.cnt[:n] > 0, hr, -1)
    stale = hr + HEAD_GATE_HORIZON < state.max_round
    if cfg.retired:
        stale = stale | jnp.asarray(retired_mask(cfg)[:n])
    return jnp.min(jnp.where(stale, INT32_MAX, hr))


def retired_mask(cfg: DagConfig) -> np.ndarray:
    """bool[N+1] trace-time constant marking retired participant
    columns (the +1 row covers the sentinel creator id ``n``).  All
    False — and therefore free at trace time — for epoch-0 configs."""
    mask = np.zeros(cfg.n + 1, bool)
    if cfg.retired:
        mask[list(cfg.retired)] = True
    return mask


def bucket(x: int, minimum: int = 8) -> int:
    """Round a capacity up to a power of two (bounds jit recompilation)."""
    v = max(x, minimum)
    return 1 << (v - 1).bit_length()


def fd_reverse_scan_wins(sched_rows: int, e_cap: int, k: int = 1) -> bool:
    """Measured v5e cost model for the two first-descendant strategies:
    the reverse level scan pays ~25 us per schedule row; the chain-view
    count was fitted at ~(k*E)^2 / 3e10 s (k = branch slots per creator —
    the fork pipeline's column axis is k*N wide).  Deep narrow DAGs favor
    the count, wide ones the scan (measured: 64x65k 3,494 levels -> count;
    1024x100k 392 levels and 256x1M -> scan, 12x at 1M).  The count's
    estimate is stale: it was fitted to the plain compare-count over
    every chain position, and ``count_below_sorted`` now searches in two
    levels at a fraction of that for chains longer than one block, so
    the line sends some deep shapes to the scan that the search would
    beat (PERF.md section 7 item 8; not re-fitted)."""
    return sched_rows < ((k * e_cap) ** 2) * 4.8e-7


#: lanes of one block of the sorted chain view (one vreg row of i32)
SORTED_BLOCK = 128
#: elements of the widest per-chunk intermediate of count_below_sorted
#: (64 MB of i32)
_COUNT_CHUNK_ELEMS = 2 ** 24


def count_below_sorted(V: jnp.ndarray, thr: jnp.ndarray) -> jnp.ndarray:
    """out[c, d, t] = |{s : V[c, s, d] < thr[d, t]}| for i32 V[C, S+1, D]
    non-decreasing along s (the chain view of both first-descendant
    fills: la along a chain only grows, INT32_MAX past its tail).

    A search in two levels over blocks of at most ``SORTED_BLOCK``
    positions: count the block maxima below each threshold (``m`` whole
    blocks lie below it), then count inside the one block where it
    lands, fetched as a single row (per-element gathers run one at a
    time on TPU; a row of 128 lanes is one fetch): nb + 128 compares per
    threshold, nb the blocks, instead of S+1.  A column no longer than
    one block is one unpadded row, so there the fine level is the plain
    count.  Both levels run in chunks of thresholds so no intermediate
    exceeds ``_COUNT_CHUNK_ELEMS``.  Equal to the plain count bit for
    bit."""
    c, s1, d = V.shape
    t_total = thr.shape[1]
    thr = thr.astype(I32)
    lanes = min(SORTED_BLOCK, s1)
    nb = -(-s1 // lanes)
    rows = jnp.pad(jnp.swapaxes(V, 1, 2),
                   ((0, 0), (0, 0), (0, nb * lanes - s1)),
                   constant_values=INT32_MAX)
    rows = rows.reshape(c * d * nb, lanes)                     # [C*D*nb, L]
    bmax = rows[:, lanes - 1].reshape(c, d, nb)
    base = (jnp.arange(c * d, dtype=I32) * nb).reshape(c, d, 1)

    def count_chunk(th):                                       # [D, Tc]
        m = (bmax[:, :, :, None] < th[None, :, None, :]).sum(
            axis=2, dtype=I32)                                 # [C, D, Tc]
        blk = jnp.minimum(m, nb - 1)
        row = rows[base + blk]                                 # [C, D, Tc, L]
        fine = (row < th[None, :, :, None]).sum(axis=3, dtype=I32)
        return blk * lanes + fine

    tc = max(1, min(t_total, _COUNT_CHUNK_ELEMS // (c * d * max(nb, lanes))))
    n_chunks = -(-t_total // tc)
    thr_p = jnp.pad(thr, ((0, 0), (0, n_chunks * tc - t_total)))
    chunks = thr_p.reshape(d, n_chunks, tc).swapaxes(0, 1)    # [n, D, Tc]
    counts = jax.lax.map(count_chunk, chunks)                 # [n, C, D, Tc]
    return jnp.moveaxis(counts, 0, 2).reshape(c, d, -1)[:, :, :t_total]


def sanitize(idx: jnp.ndarray, sentinel: int) -> jnp.ndarray:
    """Remap negative (missing) indices to the sentinel row."""
    return jnp.where(idx < 0, sentinel, idx)


def set_sentinel(a: jnp.ndarray, mask: jnp.ndarray, v) -> jnp.ndarray:
    """SPMD-safe sentinel write: ``where(mask, v, a)`` over an iota mask.

    NEVER restore a sentinel row of a (possibly sharded) array with
    ``a.at[row].set(v)``: the static-index write lowers to a
    dynamic-update-slice whose per-shard start index is *clamped* into each
    shard's local range under SPMD partitioning, so the write also lands on
    the last row of every earlier shard and corrupts real data.  Elementwise
    selects partition trivially.  Build ``mask`` as
    ``jnp.arange(dim) == sentinel`` (broadcast to the array's rank)."""
    return jnp.where(mask, jnp.asarray(v, a.dtype), a)


def repack_round_bits(cfg: DagConfig, state: DagState) -> DagState:
    """Recompute the packed per-round witness bitplanes (``mbr``,
    ``fmr``) from the wide tensors — they are pure derived caches, so
    wholesale recomputation is the one maintenance discipline that can
    never drift.  O(R·N) gather+pack: negligible next to any phase
    that changed the inputs.  Called at the end of every program that
    rewrites wslot/mbit (ingest rounds, rescan) or famous (fame)."""
    valid = state.wslot >= 0
    ws = sanitize(state.wslot, cfg.e_cap)
    mb = state.mbit[ws] & valid
    fm = (state.famous == FAME_TRUE) & valid
    return state._replace(mbr=pack_bits(mb), fmr=pack_bits(fm))


def repack_round_bits_np(cfg: DagConfig, wslot: np.ndarray,
                         famous: np.ndarray, mbit: np.ndarray):
    """Numpy twin of ``repack_round_bits`` for host-side rebuilds —
    epoch re-shapes (the lane count re-buckets when a join widens the
    participant axis) and checkpoint restore (pre-v5 checkpoints carry
    no bitplanes; v5+ ones are re-packed rather than trusted, which
    also closes the hostile inconsistent-snapshot hole).  Bit order
    matches ops/pack.py: ``np.packbits(..., bitorder="little")``."""
    valid = wslot >= 0
    ws = np.where(valid, wslot, cfg.e_cap)
    mb = mbit[np.clip(ws, 0, cfg.e_cap)] & valid
    fm = (famous == FAME_TRUE) & valid
    lp = cfg.lp
    mbr = np.packbits(mb, axis=-1, bitorder="little")[..., :lp]
    fmr = np.packbits(fm, axis=-1, bitorder="little")[..., :lp]
    return mbr.astype(np.uint8), fmr.astype(np.uint8)


# Consensus-observable tensors: every decision the pipeline emits.  The
# single source of truth for bit-parity checks (fd-mode differentials,
# sharded-vs-single-chip, the driver's multi-chip dry-run).
CONSENSUS_EVENT_FIELDS = ("la", "fd", "round", "witness", "rr", "cts")
CONSENSUS_TABLE_FIELDS = ("wslot", "famous")


def assert_consensus_parity(ref: DagState, out: DagState, n_events: int,
                            label: str = "") -> None:
    """Assert bit-identical consensus decisions between two DagStates
    (per-event fields compared on the first n_events rows)."""
    for f in CONSENSUS_EVENT_FIELDS:
        a = np.asarray(getattr(ref, f))[:n_events]
        b = np.asarray(getattr(out, f))[:n_events]
        if not (a == b).all():
            raise AssertionError(
                f"consensus parity broken{label and f' ({label})'}: "
                f"{f} differs on {int((a != b).sum())}/{a.size} entries"
            )
    for f in CONSENSUS_TABLE_FIELDS:
        a = np.asarray(getattr(ref, f))
        b = np.asarray(getattr(out, f))
        if not (a == b).all():
            raise AssertionError(
                f"consensus parity broken{label and f' ({label})'}: "
                f"{f} differs on {int((a != b).sum())}/{a.size} entries"
            )
    if int(ref.lcr) != int(out.lcr):
        raise AssertionError(
            f"consensus parity broken{label and f' ({label})'}: "
            f"lcr {int(ref.lcr)} != {int(out.lcr)}"
        )
