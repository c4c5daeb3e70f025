"""The fused live-flush program: incremental ingest + windowed fame +
windowed order in ONE compiled kernel with donated device state.

This is the streaming-incremental half of ROADMAP item 3.  The legacy
("throughput") surface runs three separate programs per flush — ingest,
then DecideFame over ALL r_cap round rows ([R, N, N] witness tensors
re-gathered every call), then DecideRoundReceived scanning ALL r_cap
rounds against the full [E+1, N] fd tensor — so per-flush cost grows
with DAG size even when one gossip sync added eight events.  The
reference avoids exactly this with its rolling caches
(hashgraph/caches.go:45-76): consensus work per sync is proportional to
*new* events.  This module is the dense twin of that idea:

- **State stays resident.**  The DagState rides through as a donated
  buffer (the ``donate_argnums`` discipline of ops/ingest.py applied to
  the whole pipeline); nothing round-trips to host between phases.
- **Fame/order resume from persisted frontiers.**  ``state.lcr`` is the
  order frontier (every decided round <= lcr has been reception-scanned
  exactly once — reception sets are frozen at decision time, see
  ``order_window_impl``) and ``state.max_round`` bounds the undecided
  window, so both phases operate on a W-round dynamic slice starting at
  lcr+1 instead of re-deriving from genesis.  W is a small static
  bucket chosen by the engine from its host mirrors (live DAGs keep
  2-4 rounds open), so a stream of gossip-sized flushes shares ONE
  compiled program.
- **Witness-set finality gate.**  Fame decisions are gated on
  ``head_round_min_math`` (the fused twin of ops/wide.py
  ``complete=False``), fixing the premature intra-round finality defect
  on the live path: a round's famous set — and therefore its prn
  whitening and cts medians — freezes only once every chain's head
  round has passed it.

Kernel working-set diet (ROADMAP item 4) — bytes ARE latency on this
path (the order phase measured 94% of HBM peak):

- **Event-axis frontier.**  The reception scans slice ``fd[f0:f0+F]``
  instead of reading the full-height ``[E+1, N]`` column per windowed
  round: every row below ``f0`` (the first slot with ``rr`` undecided,
  derived in-kernel from the persisted reception frontier the same way
  ts32 derives its rebase from the live minimum) is already received
  and can never newly receive.  ``F`` is a power-of-two bucket
  (``bucket_f``) of the live frontier height, mirrored host-side by
  the engine, so the AOT manifest stays small and ``recompile-hazard``
  stays clean.
- **Bit-packed votes.**  With ``cfg.packed`` the see/strongly-see/vote
  tensors — booleans the f32 tally path stores 4 bytes wide — ride as
  uint8 lanes along the participant axis (8:1, ops/pack.py) and every
  supermajority tally is a ``population_count`` reduction instead of an
  f32 einsum: the vote recursion's carried working set shrinks 32:1 and
  the arithmetic moves onto the int path the roofline says is waiting.
  Counts are exact integers on both paths, so the flag is bit-parity
  preserving (tests/test_diet.py pins it, coin rounds included).

Shape bucketing: one program per (cfg, W, F, kpad, tpad, bpad).  The
engine records compiled shape keys in the AOT manifest (ops/aot.py) so
a restart can pre-compile them against the persistent XLA cache.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .fame import (
    F32,
    FAME_FALSE,
    FAME_TRUE,
    FAME_UNDEFINED,
    _lcr_candidates,
)
from .ingest import EventBatch, ingest_coords_impl, ingest_rounds_impl
from .order import order_median_rows
from .pack import count_bits, pack_bits, popcount_sum
from .state import (
    DagConfig,
    DagState,
    I32,
    PER_EVENT_FIELDS,
    PER_ROUND_FIELDS,
    bucket,
    head_round_min_math,
    repack_round_bits,
    sanitize,
)

#: latency-kernel round-window buckets: W is rounded up to one of these
#: so a live stream (2-4 open rounds) shares one compiled program
W_BUCKETS = (4, 8, 16)
W_MAX = W_BUCKETS[-1]

#: smallest frontier bucket.  256 rows covers the whole undecided span
#: of a typical gossip stream (the gated engine's frontier height peaks
#: at a few hundred rows before the first commit snaps it back), so a
#: live fleet compiles ONE fused program per (W, batch) shape exactly
#: like the pre-diet kernel, while the slice still cuts a 4k-row (or
#: deeper) event axis 16x+.  Raising it trades bytes for program count;
#: the bucket ladder stays ~log2(e_cap / F_MIN) entries either way.
F_MIN = 256


def bucket_w(active_rounds: int, r_cap: int) -> int:
    """Smallest W bucket covering ``active_rounds`` open rounds, or 0
    when no latency bucket fits (the engine falls back to the
    throughput kernels)."""
    for w in W_BUCKETS:
        if active_rounds <= w and w <= r_cap:
            return w
    return 0


def bucket_f(height: int, e1: int) -> int:
    """Power-of-two frontier bucket: the event rows the windowed order
    phase must cover (live frontier height, HOST mirror — it must never
    under-count, so the engine derives it from a monotone lower bound
    on the first undecided slot).  Clamps to full height ``e1`` when
    the bucket would not fit, which is also the frontier-off pin."""
    f = bucket(max(int(height), 1), F_MIN)
    return e1 if f >= e1 else f


def fame_window_impl(
    cfg: DagConfig, W: int, state: DagState, gate: bool
) -> DagState:
    """Diagonal-scan fame voting over the W-round window starting at
    lcr+1 — the same recursion as fame.decide_fame_impl with the round
    axis sliced to the open window, so the [W, N, N] witness tensors
    replace the [R, N, N] full-table gathers.  Rounds above the window
    (max_round ran past the engine's W estimate) simply stay undecided
    until the next flush re-centers the window; fame decisions are
    sticky and votes are recomputed from insert-frozen coordinates, so
    deferral never changes a decision.

    With ``cfg.packed`` the vote recursion runs bit-packed: ss/see/vote
    tensors are uint8 lanes over the contraction axis, the tally
    ``yays[i,y,x] = popcount(ss_pk[i,y] & votes_pk[i,x])`` replaces the
    f32 einsum, and coin rounds select per-bit against the persisted
    packed witness coin plane ``state.mbr`` — identical integer counts,
    so decisions are bit-identical to the f32 path."""
    n, sm = cfg.n, cfg.super_majority
    R = cfg.r_cap

    z = jnp.zeros((), I32)
    lo = jnp.clip(state.lcr + 1 - state.r_off, 0, max(R - W, 0))
    wsl = jax.lax.dynamic_slice(state.wslot, (lo, z), (W, n))
    valid_w = wsl >= 0
    ws = sanitize(wsl, cfg.e_cap)
    law = state.la[ws]                                 # [W, N, N]
    fdw = state.fd[ws]                                 # [W, N, N]
    seqw = state.seq[ws]                               # [W, N]
    famous_w = jax.lax.dynamic_slice(state.famous, (lo, z), (W, n))

    law_next = jnp.concatenate(
        [law[1:], jnp.full((1, n, n), -1, law.dtype)], axis=0
    )
    valid_next = jnp.concatenate(
        [valid_w[1:], jnp.zeros((1, n), bool)], axis=0
    )

    ss_see = law_next[:, :, None, :] >= fdw[:, None, :, :]
    ss_cnt = count_bits(ss_see) if cfg.packed else ss_see.sum(-1)
    ss_next_b = (
        (ss_cnt >= sm) & valid_next[:, :, None] & valid_w[:, None, :]
    )
    see_next_b = (
        (law_next >= seqw[:, None, :])
        & valid_next[:, :, None]
        & valid_w[:, None, :]
    )

    # window row i holds absolute round lo + i + r_off
    i_idx = jnp.arange(W, dtype=I32) + lo + state.r_off
    in_window = (i_idx > state.lcr) & (i_idx < state.max_round)
    if gate:
        in_window = in_window & (i_idx <= head_round_min_math(cfg, state))

    d_max = jnp.minimum(
        jnp.maximum(state.max_round - jnp.maximum(state.lcr, -1), 2), W
    )

    def decide(d, famous, v, strong, can_vote):
        """Shared decision update: identical on both vote layouts."""
        undecided = (famous == FAME_UNDEFINED) & valid_w & in_window[:, None]
        normal = (d % cfg.active_n) != 0
        deciding = strong & normal & can_vote[:, None, None]
        decide_x = deciding.any(axis=1)
        v_star = (deciding & v).any(axis=1)
        famous = jnp.where(
            undecided & decide_x,
            jnp.where(v_star, FAME_TRUE, FAME_FALSE).astype(jnp.int8),
            famous,
        )
        return famous, normal

    if cfg.packed:
        LP = cfg.lp
        # contraction (voter) axis packed: ss_pk[i, y, lanes-of-w],
        # votes_pk[i, x, lanes-of-w]; the d=1 votes pack the see bits
        # over their voter axis
        ss_pk = pack_bits(ss_next_b)                        # [W, N, LP]
        tot_next = popcount_sum(ss_pk)                      # i32[W, N]
        votes0 = pack_bits(jnp.swapaxes(see_next_b, 1, 2))  # [W, N, LP]
        mb_w = jax.lax.dynamic_slice(state.mbr, (lo, z), (W, LP))

        ss_pad = jnp.concatenate(
            [ss_pk, jnp.zeros((W, n, LP), jnp.uint8)], axis=0
        )
        tot_pad = jnp.concatenate(
            [tot_next, jnp.zeros((W, n), I32)], axis=0
        )
        mb_pad = jnp.concatenate(
            [mb_w, jnp.zeros((W, LP), jnp.uint8)], axis=0
        )

        def step(d, carry):
            votes_pk, famous = carry
            d = jnp.asarray(d, I32)
            can_vote = (i_idx + d) <= state.max_round       # [W]

            ss_d = jax.lax.dynamic_slice(ss_pad, (d - 1, z, z), (W, n, LP))
            tot_d = jax.lax.dynamic_slice(tot_pad, (d - 1, z), (W, n))
            mb_d = jax.lax.dynamic_slice(mb_pad, (d, z), (W, LP))

            # the popcount supermajority tally: AND the voter lanes,
            # count bits — exact integers, no f32 einsum
            yays = popcount_sum(
                ss_d[:, :, None, :] & votes_pk[:, None, :, :]
            )                                               # i32[W, N, N]
            nays = tot_d[:, :, None] - yays
            v = yays >= nays
            strong = jnp.maximum(yays, nays) >= sm

            famous, normal = decide(d, famous, v, strong, can_vote)

            # next votes, packed over the NEW voter axis y (axis 1 of
            # v): coin rounds select per-bit against the packed
            # witness coin plane — where(strong, v, mb) per lane bit
            v_pk = pack_bits(jnp.swapaxes(v, 1, 2))         # [W, N_x, LP]
            s_pk = pack_bits(jnp.swapaxes(strong, 1, 2))
            coin_pk = (s_pk & v_pk) | (~s_pk & mb_d[:, None, :])
            new_pk = jnp.where(normal, v_pk, coin_pk)
            votes_pk = jnp.where(can_vote[:, None, None], new_pk, votes_pk)
            return votes_pk, famous

        _, famous_w = jax.lax.fori_loop(
            2, d_max + 1, step, (votes0, famous_w)
        )
    else:
        mbw = state.mbit[ws]                                # bool[W, N]
        ss_next = ss_next_b.astype(F32)
        tot_next = ss_next.sum(-1)                          # f32[W, N]
        see_next = see_next_b.astype(F32)

        zpad3 = jnp.zeros((W, n, n), F32)
        ss_pad = jnp.concatenate([ss_next, zpad3], axis=0)  # [2W, N, N]
        tot_pad = jnp.concatenate(
            [tot_next, jnp.zeros((W, n), F32)], axis=0
        )
        mb_pad = jnp.concatenate(
            [mbw, jnp.zeros((W, n), bool)], axis=0
        )

        def step(d, carry):
            votes, famous = carry
            d = jnp.asarray(d, I32)
            can_vote = (i_idx + d) <= state.max_round       # [W]

            ss_d = jax.lax.dynamic_slice(ss_pad, (d - 1, z, z), (W, n, n))
            tot_d = jax.lax.dynamic_slice(tot_pad, (d - 1, z), (W, n))
            mb_d = jax.lax.dynamic_slice(mb_pad, (d, z), (W, n))

            yays = jnp.einsum(
                "iyw,iwx->iyx", ss_d, votes, preferred_element_type=F32
            )
            nays = tot_d[:, :, None] - yays
            v = yays >= nays
            strong = jnp.maximum(yays, nays) >= sm

            famous, normal = decide(d, famous, v, strong, can_vote)

            coin_vote = jnp.where(strong, v, mb_d[:, :, None])
            new_votes = jnp.where(normal, v, coin_vote).astype(F32)
            votes = jnp.where(can_vote[:, None, None], new_votes, votes)
            return votes, famous

        _, famous_w = jax.lax.fori_loop(
            2, d_max + 1, step, (see_next, famous_w)
        )

    decided_round = ((~valid_w) | (famous_w != FAME_UNDEFINED)).all(axis=1)
    has_w = valid_w.any(axis=1)
    # gated: contiguous-prefix advance (fame._lcr_candidates) — rounds
    # the window doesn't cover are above max_round-1 or beyond the
    # gate, so the window always contains the first failing round
    cand = _lcr_candidates(state, i_idx, in_window, decided_round,
                           has_w, gate)
    new_lcr = jnp.max(jnp.where(cand, i_idx, -1))
    lcr = jnp.maximum(state.lcr, new_lcr)

    famous_out = jax.lax.dynamic_update_slice(state.famous, famous_w, (lo, z))
    # fame rewrote the famous table: refresh the packed bitplanes the
    # order phase's popcount reception tallies read
    return repack_round_bits(
        cfg, state._replace(famous=famous_out, lcr=lcr)
    )


def order_window_impl(
    cfg: DagConfig, W: int, F: int, state: DagState, lcr_prev: jnp.ndarray
) -> DagState:
    """Round-received + consensus timestamps over the W-round window
    starting at lcr_prev+1 — the only rounds that can newly receive
    events this flush — scanning only the F-row event-axis frontier.

    Exactly-once soundness (why the round window replaces the full
    R-round rescan bit-for-bit):

    - every decided round is <= lcr (lcr is the max over decided
      rounds), so rounds newly decided this call lie in
      (lcr_prev, lcr_new] — inside the window;
    - a round's reception set is frozen at decision time: see(w, x)
      needs x's first descendant on w's chain at seq <= seq(w), and
      once w is inserted its chain prefix is complete, so fd[x, c_w]
      can only ever gain values ABOVE seq(w) — no event (present or
      late-arriving) can start being seen after the round decided.
      Rounds <= lcr_prev were scanned when they decided; rescanning
      them is the identity, so the window skips them.

    Event-axis frontier soundness (why ``fd[f0:f0+F]`` replaces the
    full-height column reads bit-for-bit): only rows with ``rr == -1``
    can newly receive or write cts, and every row below ``f0`` (the
    first such slot) already has ``rr >= 0`` — received is sticky.  The
    slice offset is derived IN-KERNEL from the persisted rr tensor, so
    it is exact; the HOST picks the static height F from a monotone
    lower-bound mirror of f0 (``engine._frontier_cache``), so
    ``F >= n_events - f0`` always holds and no undecided row is ever
    above the slice (a missed row would never be rescanned — the
    exactly-once property cuts both ways)."""
    n, e1 = cfg.n, cfg.e_cap + 1
    R = cfg.r_cap

    z = jnp.zeros((), I32)
    lo = jnp.clip(lcr_prev + 1 - state.r_off, 0, max(R - W, 0))
    wsl = jax.lax.dynamic_slice(state.wslot, (lo, z), (W, n))
    valid_w = wsl >= 0
    ws = sanitize(wsl, cfg.e_cap)
    seqw = state.seq[ws]                                   # [W, N]
    fam_tab = jax.lax.dynamic_slice(state.famous, (lo, z), (W, n))
    fam = (fam_tab == FAME_TRUE) & valid_w                 # [W, N]
    decided = ((~valid_w) | (fam_tab != FAME_UNDEFINED)).all(axis=1)
    has_w = valid_w.any(axis=1)
    fam_cnt = fam.sum(axis=1)                              # [W]

    # event-axis frontier: first row whose reception is still open
    idx = jnp.arange(e1, dtype=I32)
    f0 = jnp.min(jnp.where(state.rr < 0, idx, e1))
    o = jnp.clip(f0, 0, max(e1 - F, 0))
    fd_f = jax.lax.dynamic_slice(state.fd, (o, z), (F, n))
    rr_f = jax.lax.dynamic_slice(state.rr, (o,), (F,))
    rnd_f = jax.lax.dynamic_slice(state.round, (o,), (F,))
    seq_f = jax.lax.dynamic_slice(state.seq, (o,), (F,))
    rows_f = o + jnp.arange(F, dtype=I32)
    und_f = (rows_f < state.n_events) & (seq_f >= 0) & (rr_f == -1)

    if cfg.packed:
        fmr_w = jax.lax.dynamic_slice(state.fmr, (lo, z), (W, cfg.lp))
    i_abs0 = lo + state.r_off

    def step(i, rr_cur):
        i_abs = i_abs0 + i
        active = (
            decided[i] & has_w[i] & (i_abs <= state.max_round)
            & (i_abs <= state.lcr)
        )
        sees_b = fd_f <= seqw[i][None, :]                  # [F, N]
        if cfg.packed:
            # reception supermajority by popcount: AND the packed see
            # bits against the round's famous bit plane
            c = popcount_sum(pack_bits(sees_b) & fmr_w[i][None, :])
        else:
            c = (fam[i][None, :] & sees_b).sum(axis=1)
        cond = (
            und_f
            & (rr_cur == -1)
            & (i_abs > rnd_f)
            & active
            & (c > fam_cnt[i] // 2)
        )
        return jnp.where(cond, i_abs, rr_cur)

    rr_f = jax.lax.fori_loop(0, W, step, rr_f)
    newly_f = und_f & (rr_f != -1)

    i_of = jnp.clip(rr_f - i_abs0, 0, W - 1)
    med = order_median_rows(cfg, state, seqw, fam, fd_f, i_of)
    cts_f = jax.lax.dynamic_slice(state.cts, (o,), (F,))
    cts_f = jnp.where(newly_f, med, cts_f)
    return state._replace(
        rr=jax.lax.dynamic_update_slice(state.rr, rr_f, (o,)),
        cts=jax.lax.dynamic_update_slice(state.cts, cts_f, (o,)),
    )


def live_flush_impl(
    cfg: DagConfig, W: int, F: int, gate: bool,
    state: DagState, batch: EventBatch
) -> DagState:
    """One live flush end to end: incremental ingest (coords + rounds)
    then windowed fame and order, all inside one program so the state
    never leaves the device between phases.  ``batch`` may be empty
    (k=0, the drain call when gossip stops): the ingest phases are
    no-ops on padded lanes and fame/order still advance.

    The ``named_scope`` regions carry phase attribution into the
    compiled program: HLO instructions inherit the scope name in their
    ``op_name`` metadata (ingest's nested ``la`` / ``fd`` / ``rounds``
    among them).  The v5e trace's operations carry only the instruction
    name, so a trace of the single fused launch splits its device time
    ingest/fame/order once joined with the program's text
    (``benchmark/scopes.py``).  Pure metadata — the compiled numerics
    are bit-identical with or without them."""
    with jax.named_scope("babble_ingest"):
        state = ingest_coords_impl(cfg, state, "incremental", batch)
        state = ingest_rounds_impl(cfg, state, "incremental", batch)
    lcr_prev = state.lcr
    with jax.named_scope("babble_fame"):
        state = fame_window_impl(cfg, W, state, gate)
    with jax.named_scope("babble_order"):
        return order_window_impl(cfg, W, F, state, lcr_prev)


live_flush = jax.jit(
    live_flush_impl, static_argnums=(0, 1, 2, 3), donate_argnums=(4,)
)


# ----------------------------------------------------------------------
# phase probe (ISSUE 11 (c)): the fused flush as three separately-timed
# sub-programs.  Same impl functions in the same order, so results are
# bit-identical to the single launch (tests/test_obs_device.py parity);
# each dispatch is host-synced, which is the probe's cost — a profiling
# posture (Config.phase_probe), not the default path.


def _ingest_flush_impl(cfg, state, batch):
    state = ingest_coords_impl(cfg, state, "incremental", batch)
    return ingest_rounds_impl(cfg, state, "incremental", batch)


def _fame_flush_impl(cfg, W, gate, state):
    # lcr_prev must be captured BEFORE fame advances it; returning it
    # as an output keeps it valid under input donation
    lcr_prev = state.lcr
    return fame_window_impl(cfg, W, state, gate), lcr_prev


_ingest_flush = jax.jit(
    _ingest_flush_impl, static_argnums=(0,), donate_argnums=(1,)
)
_fame_flush = jax.jit(
    _fame_flush_impl, static_argnums=(0, 1, 2), donate_argnums=(3,)
)
_order_flush = jax.jit(
    order_window_impl, static_argnums=(0, 1, 2), donate_argnums=(3,)
)


def probed_flush(cfg: DagConfig, W: int, F: int, gate: bool,
                 state: DagState, batch: EventBatch):
    """Run one live flush as three timed dispatches.  Returns
    ``(state, {"ingest_s", "fame_s", "order_s"})`` with wall times
    measured to completion (block_until_ready per phase)."""
    import time

    t0 = time.perf_counter()
    state = jax.block_until_ready(_ingest_flush(cfg, state, batch))
    t1 = time.perf_counter()
    state, lcr_prev = jax.block_until_ready(
        _fame_flush(cfg, W, gate, state)
    )
    t2 = time.perf_counter()
    state = jax.block_until_ready(_order_flush(cfg, W, F, state, lcr_prev))
    t3 = time.perf_counter()
    return state, {"ingest_s": t1 - t0, "fame_s": t2 - t1,
                   "order_s": t3 - t2}


# ----------------------------------------------------------------------
# bytes-touched estimates (ISSUE 11 (c)): a per-flush HBM-traffic model
# derived from the live DagState shapes, so ROADMAP item 4's
# frontier/bit-packing work has a before/after meter without tracing.
# These are first-order ESTIMATES of bytes moved (reads + writes of the
# dominant tensors), not measurements: each entry counts logical passes
# over one tensor.
#
# The model is FIELD-ITEMIZED (ISSUE 12): every per-event and per-round
# DagState tensor (ops/state.py PER_EVENT_FIELDS / PER_ROUND_FIELDS)
# must own a FIELD_TRAFFIC row, or the ``bytes-model-coverage`` lint
# rule fails the build — the meter stays honest as fields are added,
# instead of silently under-counting new state.  Keys beyond the
# DagState fields (the ``derived:*`` rows) model kernel temporaries
# (vote tensors, the median sort double) that dominate fame/order but
# are not persistent state.
#
# Frontier awareness (ROADMAP item 4): the order-phase rows scale with
# ``f`` — the live frontier height the kernel actually scans (F bucket
# on the latency path, e1 on the full-table surface) — and the vote
# temporaries scale with ``vb``, the bytes of one vote row (uint8 lanes
# when cfg.packed, 4-byte f32 otherwise).


class TrafficDims(NamedTuple):
    """Shape/dtype inputs to one traffic row: participant width, event
    rows, round window (W for the latency kernel, r_cap for the
    full-table surface), batch size, coordinate itemsize, frontier
    height (event rows the order scans touch), packed lane count and
    vote-row bytes."""

    n: int
    e1: int
    w: int
    k: int
    isz: int
    f: int
    lp: int
    vb: int


#: field (or ``derived:*`` temporary) -> ((phase, bytes_fn), ...).
#: bytes_fn maps TrafficDims to estimated bytes touched in that phase.
FIELD_TRAFFIC = {
    # per-event bookkeeping lanes: written once per ingested event
    "sp": (("ingest", lambda d: 4 * d.k),),
    "op": (("ingest", lambda d: 4 * d.k),),
    "creator": (("ingest", lambda d: 4 * d.k),),
    "seq": (("ingest", lambda d: 4 * d.k),
            ("fame", lambda d: 4 * d.w * d.n),       # seqw window gather
            ("order", lambda d: 4 * d.w * d.n)),
    "ts": (("ingest", lambda d: 8 * d.k),
           ("order", lambda d: 8 * d.e1)),           # median grid gather
    "mbit": (("ingest", lambda d: d.k),
             ("fame", lambda d: d.w * d.n)),         # coin-round bits
    # coordinate tensors: the dominant HBM residents.  ingest reads two
    # parent rows and writes/min-merges the new rows (~3 [N] passes
    # each); fame gathers the [W, N, N] witness tables (la twice: law +
    # law_next); order scans the F-row frontier slice of fd against
    # every window round's witnesses — the frontier diet's main cut
    # (was d.e1 rows per round before PR 14).
    "la": (("ingest", lambda d: 3 * d.k * d.n * d.isz),
           ("fame", lambda d: 2 * d.w * d.n * d.n * d.isz)),
    "fd": (("ingest", lambda d: 3 * d.k * d.n * d.isz),
           ("fame", lambda d: d.w * d.n * d.n * d.isz),
           ("order", lambda d: d.w * d.f * d.n * d.isz)),
    "round": (("ingest", lambda d: 4 * d.k),
              ("order", lambda d: 4 * d.f)),         # frontier slice read
    "witness": (("ingest", lambda d: d.k),),
    "rr": (("order", lambda d: 2 * 4 * d.f),),       # read mask + write
    "cts": (("order", lambda d: 2 * 8 * d.f),),
    # per-round tables: window slices read (famous also written back)
    "wslot": (("fame", lambda d: 4 * d.w * d.n),),
    "famous": (("fame", lambda d: 2 * d.w * d.n),),
    "sm": (("ingest", lambda d: 4 * d.k),),          # per-event threshold gather
    # packed witness bitplanes (kernel diet): coin lanes read by the
    # packed vote recursion, famous lanes by the reception popcounts;
    # both re-packed ([R+1, LP] write) by the phases that own them
    "mbr": (("fame", lambda d: 2 * d.w * d.lp),),
    "fmr": (("fame", lambda d: 2 * d.w * d.lp),
            ("order", lambda d: d.w * d.lp),),
    # kernel temporaries, not DagState fields: the ss/see/vote vote-row
    # tensors built once plus ~3 touched per diagonal vote step (vb
    # bytes per [N]-wide vote row: uint8 lanes packed, f32 wide), and
    # the order median's tv tensor + sort double over the frontier rows
    "derived:votes": (
        ("fame", lambda d: (3 * d.w + 3 * d.w * d.w) * d.n * d.vb),
    ),
    "derived:median": (("order", lambda d: 2 * 4 * d.f * d.n),),
}

# import-time twin of the bytes-model-coverage lint rule: a field that
# reaches runtime unmodeled fails here even where the linter never ran
assert set(FIELD_TRAFFIC) >= set(PER_EVENT_FIELDS) | set(PER_ROUND_FIELDS), (
    "flush traffic model is missing DagState fields: "
    f"{sorted((set(PER_EVENT_FIELDS) | set(PER_ROUND_FIELDS)) - set(FIELD_TRAFFIC))}"
)


def _traffic_estimate(cfg: DagConfig, window: int, k: int,
                      f: int, packed: bool) -> dict:
    lp = cfg.lp
    d = TrafficDims(
        n=cfg.n, e1=cfg.e_cap + 1, w=window, k=k,
        isz=int(jnp.dtype(cfg.coord_dtype).itemsize),
        f=f, lp=lp, vb=(lp if packed else 4 * cfg.n),
    )
    out = {"ingest": 0, "fame": 0, "order": 0}
    for rows in FIELD_TRAFFIC.values():
        for phase, fn in rows:
            out[phase] += int(fn(d))
    out["total"] = out["ingest"] + out["fame"] + out["order"]
    return out


def flush_bytes_estimate(cfg: DagConfig, W: int, k: int,
                         F: int | None = None) -> dict:
    """Estimated bytes touched by one fused latency flush of ``k``
    events over a W-round window and an F-row event frontier: the
    FIELD_TRAFFIC rows summed per phase — the [W, N, N] witness tensors
    and W frontier-sliced reception scans replace the full-table r_cap
    and full-height e1 passes."""
    return _traffic_estimate(cfg, W, k,
                             cfg.e_cap + 1 if F is None else F,
                             cfg.packed)


def throughput_bytes_estimate(cfg: DagConfig, k: int) -> dict:
    """Same model for the legacy full-table surface: fame re-gathers
    [R, N, N] witness tensors over all r_cap rounds and order rescans
    every round against the full [E+1, N] fd table — which is exactly
    why the windowed latency kernel exists.  Votes are modeled f32
    regardless of cfg.packed: the full-table fame tally IS the f32
    einsum (ops/fame.py keeps the reference math)."""
    return _traffic_estimate(cfg, cfg.r_cap, k, cfg.e_cap + 1, False)
