"""Event ingestion kernels: coordinate fill, first-descendant maintenance,
round assignment.

Replaces the per-event insert path of the reference (hashgraph.go:328-494)
with batched, level-scheduled scans:

- ``InitEventCoordinates`` (hashgraph.go:399-463): element-wise max-merge of
  parents' last-ancestor rows -> a gather+max over a topological level of
  events at once.
- ``UpdateAncestorFirstDescendant`` (hashgraph.go:466-494): the reference
  walks self-ancestor chains per insert, O(n·depth) store round-trips.  Here
  either (a) a vectorized ancestor-mask min-scatter per ingested batch
  (live path), or (b) a full binary-search recompute exploiting that
  ``la[ce[j, s], c]`` is monotone non-decreasing in s along each creator
  chain (batch path) — both produce identical tensors (differentially
  tested).
- ``Round``/``Witness``/``RoundInc`` (hashgraph.go:211-305) evaluated per
  topological level against the creator-indexed witness table, with
  ``StronglySee`` as a fused compare-count reduction.

Confluence note: StronglySee is insertion-time invariant — fd slots are
written exactly once (first descendant ever), and la[x] is fixed at insert,
so evaluating predicates against *final* coordinate tensors equals the
reference's incremental memoization.  This is what makes the dense batch
formulation valid.

Schedules: a batch of K new events is grouped by topological level into a
``sched[T, B]`` array of batch positions (-1 padding); all events in one
level are mutually non-ancestral so each level is one vectorized step.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pack import count_bits
from .ss import ss_counts
from .state import (
    INT32_MAX, DagConfig, DagState, I32, I64, count_below_sorted,
    repack_round_bits, retired_mask, sanitize, set_sentinel,
)

#: The ingest modes ``ingest_impl`` accepts (see its docstring).  The
#: mode is static, so an unknown one fails at trace time.
FD_MODES = ("incremental", "full", "fast", "walk")


def _check_fd_mode(fd_mode: str) -> None:
    if fd_mode not in FD_MODES:
        raise ValueError(f"unknown fd_mode {fd_mode!r}; one of {FD_MODES}")


class EventBatch(NamedTuple):
    """Host-built arrays for K new events (padded to a bucketed size).
    Parent references are device slots; events are topologically ordered."""

    sp: jnp.ndarray       # i32[K] self-parent slot, -1
    op: jnp.ndarray       # i32[K] other-parent slot, -1
    creator: jnp.ndarray  # i32[K]
    seq: jnp.ndarray      # i32[K]
    ts: jnp.ndarray       # i64[K]
    mbit: jnp.ndarray     # bool[K]
    k: jnp.ndarray        # i32 scalar: real count (<= K)
    sched: jnp.ndarray    # i32[T, B] batch positions grouped by level, -1 pad


def _reset_coord_sentinels(
    state: DagState, cfg: DagConfig, include_coords: bool = True
) -> DagState:
    """Restore the sentinel row/col of everything the *coords* phase
    writes (batch fields, la/fd, chain tables) — padding lanes dump
    writes there; gathers of missing refs must stay neutral.

    Uses ``set_sentinel`` (elementwise selects over iota masks) — see its
    docstring for why ``.at[sentinel].set()`` corrupts sharded arrays
    (observed: ce/cnt rows wiped at the clamped index on an ("ev","p")
    mesh).

    Split from the rounds-phase reset so la/fd are strictly read-only in
    the rounds program: at 10k participants they are 3.7 GB each, and any
    write (even an elementwise sentinel restore) after the round-march
    while-loop makes XLA keep remat copies of both across the loop —
    +7.5 GB of temps, an OOM on one v5e chip."""
    e, n, s = cfg.e_cap, cfg.n, cfg.s_cap
    e_row = jnp.arange(e + 1) == e        # [E+1]
    n_row = jnp.arange(n + 1) == n        # [N+1]
    s_col = jnp.arange(s + 1) == s        # [S+1]
    setv = set_sentinel

    state = state._replace(
        sp=setv(state.sp, e_row, -1),
        op=setv(state.op, e_row, -1),
        creator=setv(state.creator, e_row, n),
        seq=setv(state.seq, e_row, -1),
        ts=setv(state.ts, e_row, 0),
        mbit=setv(state.mbit, e_row, False),
        ce=setv(state.ce, n_row[:, None] | s_col[None, :], -1),
        cnt=setv(state.cnt, n_row, 0),
    )
    if include_coords:
        # the wide host-driven coords folds these two into the final
        # la/fd level steps instead (include_coords=False): la/fd must
        # not even be *arguments* of any other program, or the donated
        # pass-through costs a flaky multi-GB copy
        state = state._replace(
            la=setv(state.la, e_row[:, None], -1),
            fd=setv(state.fd, e_row[:, None], cfg.fd_inf),
        )
    return state


def _reset_round_sentinels(state: DagState, cfg: DagConfig) -> DagState:
    """Restore the sentinel rows the *rounds* phase writes (round /
    witness / order fields + witness table)."""
    e, n, r = cfg.e_cap, cfg.n, cfg.r_cap
    e_row = jnp.arange(e + 1) == e        # [E+1]
    r_row = jnp.arange(r + 1) == r        # [R+1]
    setv = set_sentinel

    return state._replace(
        round=setv(state.round, e_row, -1),
        witness=setv(state.witness, e_row, False),
        rr=setv(state.rr, e_row, -1),
        cts=setv(state.cts, e_row, 0),
        wslot=setv(state.wslot, r_row[:, None], -1),
    )


def _reset_event_sentinels(state: DagState, cfg: DagConfig) -> DagState:
    """Full sentinel restore (both phases' arrays)."""
    return _reset_round_sentinels(_reset_coord_sentinels(state, cfg), cfg)


def _write_batch_fields(state: DagState, cfg: DagConfig, b: EventBatch) -> DagState:
    kpad = b.sp.shape[0]
    pos = jnp.arange(kpad, dtype=I32)
    real = pos < b.k
    slots = jnp.where(real, state.n_events + pos, cfg.e_cap)
    c_dump = jnp.where(real, b.creator, cfg.n)
    # ce columns are seq-window-local (state docstring): col = seq - s_off[c]
    s_loc = b.seq - state.s_off[jnp.clip(b.creator, 0, cfg.n)]
    s_dump = jnp.where(real, s_loc, cfg.s_cap)
    return state._replace(
        sp=state.sp.at[slots].set(b.sp),
        op=state.op.at[slots].set(b.op),
        creator=state.creator.at[slots].set(b.creator),
        seq=state.seq.at[slots].set(b.seq),
        ts=state.ts.at[slots].set(b.ts),
        mbit=state.mbit.at[slots].set(b.mbit),
        ce=state.ce.at[c_dump, s_dump].set(slots),
        cnt=state.cnt.at[c_dump].add(jnp.where(real, 1, 0).astype(I32)),
        n_events=state.n_events + b.k,
    )


def _slot_sched(state_n0: jnp.ndarray, cfg: DagConfig, sched: jnp.ndarray) -> jnp.ndarray:
    """Schedule of batch positions -> schedule of device slots (pad -> sentinel)."""
    return jnp.where(sched >= 0, state_n0 + sched, cfg.e_cap)


def la_step_math(cfg: DagConfig, sp, op, creator, seq, la, idx):
    """One topological level of last-ancestor fill:
    la[x] = max(la[sp(x)], la[op(x)]) with own slot := own seq.
    ``idx`` are device slots (sentinel e_cap for padding lanes).
    ops/wide.py's _la_block_scan is the column-blocked twin of this
    recurrence (block-offset own-column handling; differentially
    tested against this form)."""
    spx = sanitize(sp[idx], cfg.e_cap)
    opx = sanitize(op[idx], cfg.e_cap)
    rows = jnp.maximum(la[spx], la[opx])                     # [B, N]
    own_col = jnp.clip(creator[idx], 0, cfg.n - 1)
    rows = rows.at[jnp.arange(idx.shape[0]), own_col].set(
        seq[idx].astype(rows.dtype)
    )
    return la.at[idx].set(rows)


def fd_step_math(cfg: DagConfig, sp, op, fd, idx):
    """One *reversed* topological level of first-descendant fill:
    scatter-min each event's final fd row into its parents' rows
    (blocked twin: ops/wide.py _fd_block_scan)."""
    rows = fd[idx]                                           # [B, N]
    spx = sanitize(sp[idx], cfg.e_cap)
    opx = sanitize(op[idx], cfg.e_cap)
    fd = fd.at[spx].min(rows)
    return fd.at[opx].min(rows)


def _la_level_scan(state: DagState, cfg: DagConfig, slot_sched: jnp.ndarray) -> DagState:
    """Fill last-ancestor rows one topological level at a time (fused
    lax.scan form; the wide pipeline runs the column-blocked twin)."""

    def step(la, idx):
        return la_step_math(
            cfg, state.sp, state.op, state.creator, state.seq, la, idx
        ), None

    la, _ = jax.lax.scan(step, state.la, slot_sched)
    return state._replace(la=la)


def _fd_init_own(state: DagState, cfg: DagConfig, b: EventBatch) -> DagState:
    kpad = b.sp.shape[0]
    pos = jnp.arange(kpad, dtype=I32)
    real = pos < b.k
    # slots of the just-written batch: n_events already advanced by k
    slots = jnp.where(real, state.n_events - b.k + pos, cfg.e_cap)
    own_col = jnp.clip(b.creator, 0, cfg.n - 1)
    return state._replace(
        fd=state.fd.at[slots, own_col].set(b.seq.astype(state.fd.dtype))
    )


def _fd_incremental(state: DagState, cfg: DagConfig, b: EventBatch) -> DagState:
    """For each new event e (creator c, seq q): every ancestor y gains a
    first descendant by c at q unless it already has an earlier one.
    fd[y, c] = min(fd[y, c], q) over ancestors — an O(K·E) masked min-scatter.
    fd slots are write-once (min of an INF slot), matching the reference's
    'stop at the first chain link that already has one' walk."""
    kpad = b.sp.shape[0]
    pos = jnp.arange(kpad, dtype=I32)
    real = pos < b.k
    slots = jnp.where(real, state.n_events - b.k + pos, cfg.e_cap)

    la_b = state.la[slots]                                        # [K, N]
    cy = jnp.clip(state.creator, 0, cfg.n - 1)                    # [E+1]
    valid_y = (jnp.arange(cfg.e_cap + 1) < state.n_events) & (state.seq >= 0)
    # anc[b, y]: y is ancestor of batch event b
    anc = la_b[:, cy] >= state.seq[None, :]                       # [K, E+1]
    anc = anc & valid_y[None, :] & real[:, None]

    cd = cfg.coord_dtype
    vals = jnp.where(anc, b.seq[:, None].astype(cd), cfg.fd_inf)  # [K, E+1]
    c_dump = jnp.where(real, b.creator, cfg.n)
    upd = jnp.full((cfg.e_cap + 1, cfg.n + 1), cfg.fd_inf, cd)
    upd = upd.at[:, c_dump].min(vals.T)
    return state._replace(fd=jnp.minimum(state.fd, upd[:, : cfg.n]))


def _fd_reverse_scan(
    state: DagState, cfg: DagConfig, slot_sched: jnp.ndarray
) -> DagState:
    """First-descendant fill by reverse level scan — the mirror of the la
    forward scan, for whole-DAG batches.

    Walking levels deepest-first, every event's fd row is already final
    (all its descendants live in deeper levels), so one scatter-min into
    its parents' rows closes the recurrence:

        fd[p] = elementwise-min over children c of fd[c], plus own seq

    Cost is O(E·N) like the la scan (~0.8 s at 1M events) — it replaces
    the chain-view search (_fd_full) on the batch path where the cost
    model (state.fd_reverse_scan_wins) says so.  Requires the schedule to
    cover the whole DAG (the 'fast'/'walk' batch modes); incremental and
    engine paths keep their own fd strategies."""
    def step(fd, idx):
        return fd_step_math(cfg, state.sp, state.op, fd, idx), None

    fd, _ = jax.lax.scan(step, state.fd, slot_sched[::-1])
    # pad lanes dumped mins into the sentinel row; restore it
    e_row = (jnp.arange(cfg.e_cap + 1) == cfg.e_cap)[:, None]
    return state._replace(fd=set_sentinel(fd, e_row, cfg.fd_inf))


def _fd_full(state: DagState, cfg: DagConfig) -> DagState:
    """Full first-descendant recompute via chain-view search.

    fd[y, j] = smallest s with la[ce[j, s], creator[y]] >= seq[y].  Key
    restructuring for TPU: events y of one creator c form the chain
    c with seq = 0..cnt[c]-1, and the lookup table V[j, s, c] =
    la[ce[j, s], c] is monotone non-decreasing in s — so
    searchsorted(V[j, :, c], t) == |{s : V[j, s, c] < t}|, which
    state.count_below_sorted computes by a search in two levels over
    128-position blocks (whole rows, never per-element gathers: those
    run one at a time on TPU, ~20 ns each)."""
    n, s_cap = cfg.n, cfg.s_cap
    s_off = state.s_off[:n]                                      # [N]
    cnt_w = state.cnt[:n] - s_off                                # windowed lengths
    cej = state.ce[:n]                                           # [N, S+1]
    s_idx = jnp.arange(s_cap + 1)

    # V[j, s, c] = la[chain_j[s], c], +INF past the chain tail so each
    # (j, c) column stays sorted along s.  s is a window-local position;
    # la values stay absolute seqs.
    V = state.la[sanitize(cej, cfg.e_cap)].astype(I32)           # [N, S+1, N]
    V = jnp.where(
        (s_idx[None, :] < cnt_w[:, None])[:, :, None], V, INT32_MAX
    )

    # out[j, c, t] = |{s : V[j, s, c] < seq(c's event at window pos t)}|;
    # the threshold is the absolute seq t + s_off[c]
    out = count_below_sorted(V, s_idx[None, :] + s_off[:, None])
    found = out < cnt_w[:, None, None]
    # fd values are absolute seqs: window-local count + chain j's offset
    # INF must be the coordinate dtype's sentinel: a raw INT32_MAX
    # would wrap to -1 under an int16 cast at the scatter below
    out = jnp.where(
        found, out + s_off[:, None, None], jnp.asarray(cfg.fd_inf, I32)
    )

    # scatter back to event rows: fd[ce[c, t], j] = out[j, c, t]
    out_ctj = out.transpose(1, 2, 0).astype(cfg.coord_dtype)     # [N(c), T, N(j)]
    tgt = jnp.where(
        s_idx[None, :] < cnt_w[:, None], cej, cfg.e_cap
    )                                                            # [N, S+1]
    fd_new = state.fd.at[tgt].set(out_ctj)
    e_row = (jnp.arange(cfg.e_cap + 1) == cfg.e_cap)[:, None]
    return state._replace(fd=set_sentinel(fd_new, e_row, cfg.fd_inf))


def _rounds_level_scan(
    state: DagState, cfg: DagConfig, slot_sched: jnp.ndarray, raw_sched: jnp.ndarray
) -> DagState:
    """Assign round + witness per topological level (hashgraph.go:211-305):

    parent_round = max(round[sp], round[op])      (roots: 0)
    inc          = |{j : strongly_see(x, w_{parent_round, j})}| >= sm[pr]
    round        = parent_round + inc
    witness      = no self-parent, or round > round[sp]

    The increment threshold is gathered PER PARENT ROUND from
    ``state.sm`` (membership plane): round p's witness quorum belongs
    to the epoch that owns round p, so an old-epoch straggler inserted
    after an epoch transition is assigned the same round on every
    replica.  Uniform configs (no transitions) gather a constant array
    and behave exactly as the static ``cfg.super_majority`` did.
    Retired creators' events never register in the witness tables of
    rounds they are retired for (the static ``retired_mask`` dump) —
    their chains are frozen history, not fame candidates.
    """
    n = cfg.n
    retired = jnp.asarray(retired_mask(cfg))       # trace-time constant

    def step(carry, sched_rows):
        rnd, wit, wslot, max_round = carry
        idx, raw = sched_rows
        real = raw >= 0
        spx = sanitize(state.sp[idx], cfg.e_cap)
        opx = sanitize(state.op[idx], cfg.e_cap)
        is_root = (state.sp[idx] < 0) & (state.op[idx] < 0)
        pr = jnp.maximum(rnd[spx], rnd[opx])
        pr = jnp.where(is_root, 0, pr)

        # parent rounds below the rolled window gather the sentinel row
        # (those rounds are decided; see the w_row comment below)
        pr_loc = jnp.where(pr >= state.r_off, pr - state.r_off, cfg.r_cap)
        wsl = wslot[jnp.clip(pr_loc, 0, cfg.r_cap)]               # [B, N]
        fdw = state.fd[sanitize(wsl, cfg.e_cap)]                  # [B, N, N]
        la_x = state.la[idx]                                      # [B, N]
        ss_see = la_x[:, None, :] >= fdw                          # [B, N, N]
        # packed diet: the per-participant see bits tally by popcount
        # over uint8 lanes instead of a widening bool sum — identical
        # integers, 8:1 smaller reduction input (ops/pack.py)
        ss_cnt = count_bits(ss_see) if cfg.packed else ss_see.sum(-1)
        sm_x = state.sm[jnp.clip(pr_loc, 0, cfg.r_cap)]           # [B]
        ss = (ss_cnt >= sm_x[:, None]) & (wsl >= 0)
        inc = ss.sum(-1) >= sm_x
        r_x = pr + inc.astype(I32)
        w_x = (state.sp[idx] < 0) | (r_x > rnd[spx])

        rnd = rnd.at[idx].set(jnp.where(real, r_x, -1))
        wit = wit.at[idx].set(w_x & real)
        # r_x < r_off can only happen for pathological laggard events whose
        # parents both sit below the rolled round window; those rounds are
        # long decided, so (like the reference's pendingRounds pop) a late
        # witness there is never voted on — dump the write, never let the
        # negative index clamp into row 0.  Retired creators dump too:
        # a departed member's events stay orderable ancestry but must
        # not enter any NEW round's witness set.
        w_row = jnp.where(
            w_x & real & (r_x >= state.r_off)
            & ~retired[jnp.clip(state.creator[idx], 0, n)],
            r_x - state.r_off, cfg.r_cap,
        )
        w_col = jnp.clip(state.creator[idx], 0, n - 1)
        wslot = wslot.at[w_row, w_col].set(idx)
        max_round = jnp.maximum(max_round, jnp.max(jnp.where(real, r_x, -1)))
        return (rnd, wit, wslot, max_round), None

    (rnd, wit, wslot, max_round), _ = jax.lax.scan(
        step,
        (state.round, state.witness, state.wslot, state.max_round),
        (slot_sched, raw_sched),
    )
    return state._replace(round=rnd, witness=wit, wslot=wslot, max_round=max_round)


def frontier_init(state: DagState, cfg: DagConfig):
    """Initial carry of the witness-frontier march."""
    n, r_cap = cfg.n, cfg.r_cap
    cnt = state.cnt[:n] - state.s_off[:n]
    pos0 = jnp.where(cnt > 0, 0, INT32_MAX)
    pos_table0 = jnp.full((r_cap + 1, n), INT32_MAX, I32).at[0].set(pos0)
    return pos0, pos_table0


def frontier_step_math(
    state: DagState, cfg: DagConfig, r: jnp.ndarray,
    pos: jnp.ndarray, pos_table: jnp.ndarray,
):
    """One frontier-march round step (shared between the fused while-loop
    form and the host-driven wide pipeline): advance pos[j] — the seq of
    the first chain-j event with round >= r — to round r+1.

    Returns (pos_next, pos_table, any_next)."""
    n, sm, s_cap, r_cap = cfg.n, cfg.super_majority, cfg.s_cap, cfg.r_cap
    s_off = state.s_off[:n]
    cnt = state.cnt[:n] - s_off                            # windowed lengths
    cej = state.ce[:n]                                     # [N, S+1]
    rows = jnp.arange(n)
    bisect_iters = max(1, (s_cap + 1).bit_length())

    valid_w = pos < cnt
    ws = cej[rows, jnp.clip(pos, 0, s_cap)]
    fdw = state.fd[sanitize(jnp.where(valid_w, ws, -1), cfg.e_cap)]

    # bisection for the first self-inc position per chain
    lo = jnp.where(valid_w, pos, cnt)
    hi = cnt
    for _ in range(bisect_iters):
        mid = (lo + hi) >> 1
        xs = cej[rows, jnp.clip(mid, 0, s_cap)]
        lax_rows = state.la[sanitize(xs, cfg.e_cap)]   # [N, N]
        # blocked strongly-see (ops.ss): this path only runs on fresh
        # states (window offsets zero — see the docstring), which is
        # exactly the one-hot MXU path's validity condition
        ss_cnt = ss_counts(lax_rows, fdw, s_cap, batch_window=True)
        ss = (ss_cnt >= sm) & valid_w[None, :]
        ok = ss.sum(-1) >= sm
        active = lo < hi
        hi = jnp.where(ok & active, mid, hi)
        lo = jnp.where(~ok & active, mid + 1, lo)
    s_star = lo
    found = s_star < cnt

    # descent inheritance: fd rows of the per-chain first inc events
    # (fd values are absolute seqs -> window-local positions)
    e_star = cej[rows, jnp.clip(s_star, 0, s_cap)]
    fde = state.fd[sanitize(jnp.where(found, e_star, -1), cfg.e_cap)]
    inherit = fde.min(axis=0).astype(I32)              # [N] absolute
    inherit = jnp.where(
        inherit >= int(cfg.fd_inf), INT32_MAX, inherit - s_off
    )
    pos_next = jnp.minimum(
        jnp.where(found, s_star, INT32_MAX), inherit
    )
    pos_next = jnp.maximum(pos_next, pos)  # monotone safety
    any_next = (pos_next < cnt).any()
    pos_table = pos_table.at[jnp.minimum(r + 1, r_cap)].set(pos_next)
    return pos_next, pos_table, any_next


def frontier_finalize(
    state: DagState, cfg: DagConfig, pos_table: jnp.ndarray
) -> DagState:
    """Derive per-event rounds, witness flags and the witness table from
    the finished frontier position table."""
    n, s_cap, r_cap = cfg.n, cfg.s_cap, cfg.r_cap
    cnt = state.cnt[:n] - state.s_off[:n]
    cej = state.ce[:n]
    rows = jnp.arange(n)

    # per-event rounds from the pos table: round(x) = |{r : pos[r, c] <= seq}| - 1
    e1 = cfg.e_cap + 1
    c_x = jnp.clip(state.creator, 0, n - 1)
    wseq = state.seq - state.s_off[c_x]                    # window-local seqs
    pos_c = pos_table[:, c_x]                              # [R+1, E+1]
    rnd = (pos_c <= wseq[None, :]).sum(0).astype(I32) - 1 + state.r_off
    valid_e = (jnp.arange(e1) < state.n_events) & (state.seq >= 0)
    rnd = jnp.where(valid_e, rnd, -1)

    # rolled windows: the pos table starts at round r_off, so an
    # unordered laggard whose true round predates it would clamp to
    # r_off-1 — keep its stored round/witness instead (exact: rounds
    # are append-invariant).  No-op on fresh states (r_off == 0).
    stale = valid_e & (state.round >= 0) & (state.round < state.r_off)
    rnd = jnp.where(stale, state.round, rnd)

    wit = valid_e & (
        pos_table[jnp.clip(rnd - state.r_off, 0, r_cap), c_x] == wseq
    )
    wit = jnp.where(stale, state.witness, wit)

    # exact witness table: chain j's round-r witness exists iff the
    # frontier strictly advances past it
    pos_nxt = jnp.concatenate(
        [pos_table[1:], jnp.full((1, n), INT32_MAX, I32)], axis=0
    )
    w_valid = (pos_table < jnp.minimum(pos_nxt, cnt[None, :]))
    w_slots = cej[rows[None, :], jnp.clip(pos_table, 0, s_cap)]
    wslot_new = jnp.where(w_valid, w_slots, -1)[: r_cap + 1]

    max_round = jnp.max(jnp.where(valid_e, rnd, -1))
    return state._replace(
        round=rnd, witness=wit, wslot=wslot_new, max_round=max_round
    )


def _rounds_frontier(state: DagState, cfg: DagConfig) -> DagState:
    """Round assignment as a per-round witness-frontier march —
    O(actual rounds) sequential steps instead of O(levels).

    pos[r, j] := seq of the first chain-j event with round >= r.  Step r
    advances the frontier: an event has round >= r+1 iff it strongly sees
    a supermajority of round-r witnesses (round(x) = parentRound + inc,
    hashgraph.go:263-305) or descends from such an event.  Within a chain
    both the strongly-see count and descent are monotone in seq, so the
    first self-inc position is a bisection over the chain and descent
    inheritance is fd of the per-chain first inc events.

    Candidate witnesses whose true round exceeds r ("jumps" via the other
    parent) are harmless in the supermajority count: any event that
    strongly sees a jumped candidate also descends from the candidate's
    round>r ancestor and is therefore in the >=r+1 region regardless.
    Exact witness tables are derived from pos afterwards, so fame voting
    only ever sees true round-r witnesses.

    Window note: the march starts from each chain's window base and round
    r_off, so it is only exact when the window base IS the round-r_off
    witness frontier — true for fresh states (all offsets zero), which is
    the only way the engine reaches this path ('fast'/'walk' batch
    modes).  The live rolled-window path uses the incremental level scan.

    NB for wide participant axes: data-dependent gathers from the [E, N]
    la/fd tensors inside ANY device loop (while/scan/fori) make XLA keep
    layout-transposed copies of the whole operand — +7.5 GB at 10k
    participants (measured; see ops/wide.py).  This fused while-loop form
    is therefore for moderate N; the wide pipeline drives the same
    frontier_step_math from a host loop."""
    r_cap = cfg.r_cap
    pos0, pos_table0 = frontier_init(state, cfg)

    def step(carry):
        r, pos, pos_table, _ = carry
        pos_next, pos_table, any_next = frontier_step_math(
            state, cfg, r, pos, pos_table
        )
        return r + 1, pos_next, pos_table, any_next

    def cond(carry):
        r, _, _, alive = carry
        return alive & (r < r_cap - 1)

    _, _, pos_table, _ = jax.lax.while_loop(
        cond, step, (jnp.asarray(0, I32), pos0, pos_table0,
                     jnp.asarray(True))
    )
    return frontier_finalize(state, cfg, pos_table)


def ingest_coords_impl(
    cfg: DagConfig, state: DagState, fd_mode: str, batch: EventBatch
) -> DagState:
    """Phase 1 of ingest: write batch fields and fill the la/fd
    coordinate tensors (everything before round assignment).

    The last-ancestor fill runs under ``named_scope("la")`` and the
    first-descendant fill under ``named_scope("fd")``, whichever form
    the mode takes, so a profile splits ingest's device time by fill."""
    _check_fd_mode(fd_mode)
    state = _write_batch_fields(state, cfg, batch)

    def _fd_batch(state, slot_sched):
        # both strategies are bit-identical (differentially tested);
        # choice by the measured cost model in state.fd_reverse_scan_wins
        from .state import fd_reverse_scan_wins

        if fd_reverse_scan_wins(batch.sched.shape[0], cfg.e_cap):
            return _fd_reverse_scan(state, cfg, slot_sched)
        return _fd_full(state, cfg)

    if fd_mode == "walk":
        from .pallas_ingest import la_walk, unpack_la, walk_supported

        assert walk_supported(cfg.n, cfg.e_cap, cfg.s_cap), cfg
        interpret = jax.default_backend() != "tpu"
        with jax.named_scope("la"):
            packed = la_walk(
                cfg.e_cap, cfg.n, state.sp, state.op, state.creator,
                state.seq, state.n_events, interpret,
            )
            state = state._replace(
                la=unpack_la(cfg.e_cap, cfg.n, packed, state.n_events)
                .astype(cfg.coord_dtype)
            )
        state = _fd_init_own(state, cfg, batch)
        slot_sched = _slot_sched(state.n_events - batch.k, cfg, batch.sched)
        with jax.named_scope("fd"):
            state = _fd_batch(state, slot_sched)
        return _reset_coord_sentinels(state, cfg)
    slot_sched = _slot_sched(state.n_events - batch.k, cfg, batch.sched)
    with jax.named_scope("la"):
        state = _la_level_scan(state, cfg, slot_sched)
    state = _fd_init_own(state, cfg, batch)
    with jax.named_scope("fd"):
        if fd_mode == "incremental":
            state = _fd_incremental(state, cfg, batch)
        elif fd_mode == "fast":
            # batch path: the schedule covers the whole DAG, so the
            # cheaper of reverse scan / chain-view search applies (_fd_batch)
            state = _fd_batch(state, slot_sched)
        elif fd_mode == "full":
            state = _fd_full(state, cfg)
    return _reset_coord_sentinels(state, cfg)


def ingest_rounds_impl(
    cfg: DagConfig, state: DagState, fd_mode: str, batch: EventBatch
) -> DagState:
    """Phase 2 of ingest: round/witness assignment + sentinel reset.
    Composes with ingest_coords_impl; split so the 10k-participant
    configs can run each phase as its own program (la/fd then cross the
    boundary as donated arguments instead of XLA remat-copy temps —
    one such copy was 3.8 GB at 10k x 100k).  Runs under
    ``named_scope("rounds")``."""
    _check_fd_mode(fd_mode)
    with jax.named_scope("rounds"):
        if fd_mode in ("walk", "fast"):
            state = _rounds_frontier(state, cfg)
        elif fd_mode in ("incremental", "full"):
            slot_sched = _slot_sched(
                state.n_events - batch.k, cfg, batch.sched
            )
            state = _rounds_level_scan(state, cfg, slot_sched, batch.sched)
        # the rounds phase rewrote the witness tables: refresh the packed
        # per-round bitplanes (derived caches — see state.repack_round_bits)
        return repack_round_bits(cfg, _reset_round_sentinels(state, cfg))


def ingest_impl(cfg: DagConfig, state: DagState, fd_mode: str, batch: EventBatch) -> DagState:
    """Ingest a topologically-ordered batch of events end to end.

    fd_mode:
    - 'incremental' — O(K·E) fd min-scatter + level-scan rounds (live
      gossip path; small batches, shallow schedules).
    - 'full'        — chain-view fd searchsorted + level-scan rounds.
    - 'fast'        — chain-view fd + per-round frontier rounds (the
      batch/simulation path; identical outputs, differentially tested).
    - 'walk'        — like 'fast' but la is filled by the Pallas
      sequential-walk kernel (pallas_ingest.la_walk) instead of the level
      scan: one in-VMEM pass over the slot order, ~1.8x faster than the
      ~3,500-launch scan at 64x65k.  Gated by walk_supported().

    Any other mode raises ValueError at trace time (FD_MODES).
    """
    state = ingest_coords_impl(cfg, state, fd_mode, batch)
    return ingest_rounds_impl(cfg, state, fd_mode, batch)


ingest = jax.jit(ingest_impl, static_argnums=(0, 2), donate_argnums=(1,))


def rescan_rounds_impl(
    cfg: DagConfig, state: DagState, sched: jnp.ndarray
) -> DagState:
    """Re-run round assignment for a level-grouped schedule of suspect
    slots (engine._repair_rounds): used after growing r_cap, when writes
    at rounds past the old capacity were clipped.  Resets the suspects'
    round/witness, then replays the level scan against the intact lower
    witness rows."""
    e1 = cfg.e_cap + 1
    raw = sched
    slots = jnp.where(raw >= 0, raw, cfg.e_cap)
    mask = jnp.zeros((e1,), bool).at[slots.ravel()].max(raw.ravel() >= 0)
    mask = jnp.where(jnp.arange(e1) == cfg.e_cap, False, mask)
    rnd = jnp.where(mask, -1, state.round)
    wit = state.witness & ~mask
    live = (jnp.arange(e1) < state.n_events) & (state.seq >= 0)
    state = state._replace(
        round=rnd,
        witness=wit,
        max_round=jnp.max(jnp.where(live, rnd, -1)),
    )
    state = _rounds_level_scan(state, cfg, slots, raw)
    # The scan's padded lanes dumped slot indices into wslot row r_cap (and
    # -1/False into event row e_cap); restore the sentinels like every
    # ingest path does, or a later compact() gather would roll the dirty
    # dump row into live round rows as phantom witnesses.
    e_row = jnp.arange(e1) == cfg.e_cap
    r_row = (jnp.arange(cfg.r_cap + 1) == cfg.r_cap)[:, None]
    state = state._replace(
        round=set_sentinel(state.round, e_row, -1),
        witness=set_sentinel(state.witness, e_row, False),
        wslot=set_sentinel(state.wslot, r_row, -1),
    )
    return repack_round_bits(cfg, state)


rescan_rounds = jax.jit(rescan_rounds_impl, static_argnums=(0,), donate_argnums=(1,))
