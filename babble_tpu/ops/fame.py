"""DecideFame: virtual voting as a diagonal vote scan.

The reference's hottest loop (hashgraph.go:598-664) is a quadruple loop —
rounds i x voting rounds j x witnesses x x witnesses y — with a per-pair
StronglySee.  Lifted to TPU:

- Witness tensors are creator-indexed: ``law/fdw[R, N, N]`` gather the
  coordinate rows of every round's witnesses once.
- ``ss_next[r, a, b]`` (does round-(r+1) witness a strongly see round-r
  witness b) and ``see_next[r, a, x]`` (direct votes at distance 1) are
  precomputed as fused compare-count reductions.
- The vote recursion runs over the *diagonal* d = j - i: at step d every
  undecided round i is voted on by round i+d simultaneously.  The tally
      yays[i, y, x] = sum_w ss[i+d-1, y, w] * votes[i, w, x]
  is a batched (R, N, N) @ (R, N, N) matmul in f32 — MXU work; counts stay
  exact (N < 2^24).
- Normal rounds (d % N != 0) decide at a supermajority tally; coin rounds
  flip undecided votes on the middle bit of the voter's hash
  (hashgraph.go:643-649).

Decisions are sticky (see oracle.py divergence note 1): all deciding voters
provably agree within a round (two supermajorities of the same witness set
overlap), so decision order is immaterial.

The scan stops at the first d where no undecided in-window witness has a
voting round i + d left.  Both that set and the rows able to vote only
shrink as d grows, so no later step could change a decision: the early
exit is exact.  Every round decides within a few voting rounds, so the
scan takes a handful of steps where its bound, max_round - lcr, is the
DAG's whole round count on a fresh state.

After voting, the last-consensus-round advances to the highest round in the
window whose witnesses are all decided (hashgraph.go:654-673).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ss import ss_counts
from .state import (
    FAME_FALSE,
    FAME_TRUE,
    FAME_UNDEFINED,
    DagConfig,
    DagState,
    I32,
    head_round_min_math,
    repack_round_bits,
    sanitize,
)

F32 = jnp.float32
BF16 = jnp.bfloat16


def decide_fame_impl(cfg: DagConfig, state: DagState,
                     gate: bool = False) -> DagState:
    """Unjitted body — composable under an outer jit (graft entry, sharded
    pipeline).  Use ``decide_fame`` for the standalone jitted form.

    ``gate=True`` (static) applies the witness-set finality gate the
    wide pipeline decides behind (ops/wide.py ``complete=False``): a
    round's fame may only be DECIDED once every chain's head round has
    passed it (state.head_round_min_math), i.e. once its witness set is
    provably final.  Without the gate, a round whose late witness is
    still in flight can decide, freeze its famous set, and commit —
    after which the late witness lands famous=UNDEFINED on this node
    but FAME_TRUE/FALSE on a node that saw it in time, permuting the
    round's prn whitening and cts medians across honest nodes (the
    ROADMAP "premature intra-round finality" defect; chaos slow-peer
    seed 1).  The live engine runs gated; whole-DAG batch/sim paths
    keep the ungated reference semantics (every witness has arrived by
    construction, so the gate would only defer the top rounds)."""
    famous, _ = diagonal_vote_scan(cfg, state, gate)
    famous_out = state.famous.at[:cfg.r_cap].set(famous)
    # fame rewrote the famous table: refresh the packed bitplanes so
    # the order phase's popcount reception tallies read fresh lanes
    return repack_round_bits(cfg, state._replace(
        famous=famous_out,
        lcr=fame_advance_lcr(cfg, state, famous_out, gate),
    ))


def diagonal_vote_scan(cfg: DagConfig, state: DagState, gate: bool = False):
    """The diagonal vote recursion of ``decide_fame_impl``.

    Returns ``(famous, steps)``: the decided ``famous[:r_cap]`` rows and
    the number of diagonal steps taken, at most ``d_max - 1``.  The scan
    runs while some undecided in-window witness still has a voting round
    (module docstring)."""
    n, r_cap, sm = cfg.n, cfg.r_cap, cfg.super_majority
    R = r_cap

    wsl = state.wslot[:R]                              # i32[R, N]
    valid_w = wsl >= 0
    ws = sanitize(wsl, cfg.e_cap)
    law = state.la[ws]                                 # i32[R, N, N]
    fdw = state.fd[ws]                                 # i32[R, N, N]
    seqw = state.seq[ws]                               # i32[R, N]
    mbw = state.mbit[ws]                               # bool[R, N]

    # law rows of the *next* round, aligned to index r (sentinel -1 rows past end)
    law_next = jnp.concatenate(
        [law[1:], jnp.full((1, n, n), -1, law.dtype)], axis=0
    )
    valid_next = jnp.concatenate([valid_w[1:], jnp.zeros((1, n), bool)], axis=0)

    # ss_next[r, a, b]: witness a of round r+1 strongly sees witness b of round r
    ss_cnt = (law_next[:, :, None, :] >= fdw[:, None, :, :]).sum(-1)   # [R, N, N]
    ss_next = (
        (ss_cnt >= sm) & valid_next[:, :, None] & valid_w[:, None, :]
    ).astype(F32)
    tot_next = ss_next.sum(-1)                         # f32[R, N]

    # see_next[r, a, x]: witness a of round r+1 sees witness x of round r
    see_next = (
        (law_next >= seqw[:, None, :])
        & valid_next[:, :, None]
        & valid_w[:, None, :]
    ).astype(F32)

    # zero-padded doubles so a dynamic_slice at offset d stays in range
    zpad3 = jnp.zeros((R, n, n), F32)
    ss_pad = jnp.concatenate([ss_next, zpad3], axis=0)        # [2R, N, N]
    tot_pad = jnp.concatenate([tot_next, jnp.zeros((R, n), F32)], axis=0)
    mb_pad = jnp.concatenate([mbw, jnp.zeros((R, n), bool)], axis=0)

    # table row i holds absolute round i + r_off (rolling round window)
    i_idx = jnp.arange(R, dtype=I32) + state.r_off
    in_window = (i_idx > state.lcr) & (i_idx < state.max_round)
    if gate:
        in_window = in_window & (i_idx <= head_round_min_math(cfg, state))

    def open_rows(d, famous):
        # voting round j = i + d exists only while j <= max_round
        can_vote = (i_idx + d) <= state.max_round                   # [R]
        undecided = (famous == FAME_UNDEFINED) & valid_w & in_window[:, None]
        return can_vote, undecided

    d_max = jnp.maximum(state.max_round - jnp.maximum(state.lcr, -1), 2)

    def cond(carry):
        d, _, famous = carry
        can_vote, undecided = open_rows(d, famous)
        return (d <= d_max) & (undecided & can_vote[:, None]).any()

    def step(carry):
        d, votes, famous = carry
        can_vote, undecided = open_rows(d, famous)

        z = jnp.zeros((), I32)
        ss_d = jax.lax.dynamic_slice(ss_pad, (d - 1, z, z), (R, n, n))
        tot_d = jax.lax.dynamic_slice(tot_pad, (d - 1, z), (R, n))
        mb_d = jax.lax.dynamic_slice(mb_pad, (d, z), (R, n))

        yays = jnp.einsum(
            "iyw,iwx->iyx", ss_d, votes, preferred_element_type=F32
        )
        nays = tot_d[:, :, None] - yays
        v = yays >= nays
        t = jnp.maximum(yays, nays)
        strong = t >= sm                                            # [R, N, N]

        # coin-round period = number of real participants (hashgraph.go:643)
        normal = (d % cfg.active_n) != 0

        deciding = strong & normal & can_vote[:, None, None]
        decide_x = deciding.any(axis=1)                             # [R, N]
        v_star = (deciding & v).any(axis=1)                         # agree (proof in oracle)
        famous = jnp.where(
            undecided & decide_x,
            jnp.where(v_star, FAME_TRUE, FAME_FALSE).astype(jnp.int8),
            famous,
        )

        coin_vote = jnp.where(strong, v, mb_d[:, :, None])
        new_votes = jnp.where(normal, v, coin_vote).astype(F32)
        votes = jnp.where(can_vote[:, None, None], new_votes, votes)
        return d + 1, votes, famous

    d0 = jnp.asarray(2, I32)
    d, _, famous = jax.lax.while_loop(
        cond, step, (d0, see_next, state.famous[:R])
    )
    return famous, d - d0


def _lcr_candidates(state, i_idx, in_window, decided_round, has_w,
                    gate: bool):
    """Rounds lcr may advance to.

    Ungated (reference semantics, hashgraph.go:654-673): every decided
    in-window round — the max can JUMP an undecided round, permanently
    abandoning it (fame only votes rounds > lcr).

    Gated (live semantics): the CONTIGUOUS decided prefix only.  Which
    rounds decide at a given flush depends on which voting-round
    witnesses have arrived — per-node timing — so the jump converts
    decision timing into per-node round-received splits: a node that
    decided round r in time receives events there (rr=r), one whose
    lcr jumped r receives them a round later (rr=r+1), and the fleet
    commits the same events under different prn/cts cohorts (the
    OBSERVED half of the premature-finality defect; chaos slow-peer
    seed 1, events 52-54).  Stopping at the first undecided round
    keeps it votable (in_window = i > lcr), so every node eventually
    decides it with the gate-final witness set and assigns identical
    rr."""
    if not gate:
        return in_window & decided_round & has_w
    passing = in_window & decided_round
    fail = (i_idx > state.lcr) & ~passing
    first_fail = jnp.min(
        jnp.where(fail, i_idx, jnp.iinfo(I32).max)
    )
    return passing & has_w & (i_idx < first_fail)


decide_fame = jax.jit(decide_fame_impl, static_argnums=(0, 2),
                      donate_argnums=(1,))


# diagonal-scan working-set bound (elements of [R, N, N]) above which the
# round-serial blockwise form takes over; module-level so tests can force
# the block path at small shapes
BLOCK_FAME_THRESHOLD = 1 << 28


def fame_mode(cfg: DagConfig) -> str:
    """Static dispatch: the diagonal scan precomputes [R, N, N] witness
    tensors — ~6.4 GB each at N=10k, R=16 (VERDICT r2 missing #1) — so
    past ~1 GB of diagonal working set the round-serial blockwise form
    takes over."""
    return "block" if cfg.r_cap * cfg.n * cfg.n > BLOCK_FAME_THRESHOLD \
        else "diag"


def decide_fame_block_impl(
    cfg: DagConfig, state: DagState, batch_window: bool = True,
    gate: bool = False,
) -> DagState:
    """Memory-blocked DecideFame for wide participant axes.

    Same semantics as decide_fame_impl (reference hashgraph.go:598-664),
    restructured so nothing of shape [R, N, N] ever exists:

    - The vote recursion for round i reads only witness *coordinates* of
      rounds i..max_round — never another round's fame — so rounds are
      independent and the outer axis can be serialized (a fori over the
      undecided window) with O(N^2) live memory, instead of the diagonal
      scan's all-rounds-at-once [R, N, N] working set.
    - Each voting step's strongly-see matrix between consecutive-round
      witnesses comes from ops.ss.ss_counts (int8 one-hot MXU matmul at
      wide N; chunked VPU compare-reduce otherwise).
    - The vote tally is a bf16 matmul with f32 accumulation — operands
      are 0/1 and counts stay < 2^24, so it is exact.

    Voting for round i stops as soon as all its witnesses are decided
    (the diagonal scan steps all rounds at once, masking the decided
    ones, until the last open round decides); fame decisions are
    sticky, so outputs are bit-identical (differentially tested against
    decide_fame_impl and the oracle).

    ``batch_window`` (static) asserts the all-offsets-zero invariant the
    one-hot path needs; pass False on rolled-window (live) states.
    """
    R = cfg.r_cap

    def round_body(i, famous_tab):
        i_abs = i + state.r_off
        votes0, famous_i, valid_i = fame_round_init(
            cfg, state, i, famous_tab
        )

        def cond(c):
            d, _, famous_i = c
            und = (famous_i == FAME_UNDEFINED) & valid_i
            return und.any() & (i_abs + d <= state.max_round)

        def body(c):
            d, votes, famous_i = c
            votes, famous_i = fame_vote_math(
                cfg, state, i, d, votes, famous_i, valid_i, batch_window
            )
            return d + 1, votes, famous_i

        _, _, famous_i = jax.lax.while_loop(
            cond, body, (jnp.asarray(2, I32), votes0, famous_i)
        )
        return jax.lax.dynamic_update_slice_in_dim(
            famous_tab, famous_i[None, :], i, 0
        )

    lo = jnp.clip(state.lcr + 1 - state.r_off, 0, R)
    hi_abs = state.max_round
    if gate:
        # witness-set finality gate (see decide_fame_impl docstring):
        # only rounds every chain's head has passed may decide
        hi_abs = jnp.minimum(
            hi_abs, head_round_min_math(cfg, state) + 1
        )
    hi = jnp.clip(hi_abs - state.r_off, 0, R)
    famous_out = jax.lax.fori_loop(lo, hi, round_body, state.famous)
    return repack_round_bits(cfg, state._replace(
        famous=famous_out, lcr=fame_advance_lcr(cfg, state, famous_out, gate)
    ))


def fame_round_init(
    cfg: DagConfig, state: DagState, i, famous_tab
):
    """Per-round voting setup: d=1 direct see votes by round i+1
    witnesses (creator-indexed columns, matching the diagonal scan's
    see_next).  Returns (votes0, famous_i, valid_i)."""
    e_cap = cfg.e_cap
    ws_i = _wrow(state.wslot, i)
    valid_i = ws_i >= 0
    seqw_i = state.seq[sanitize(ws_i, e_cap)]
    famous_i = _wrow(famous_tab, i)

    ws_1 = _wrow(state.wslot, i + 1)
    valid_1 = ws_1 >= 0
    law_1 = state.la[sanitize(ws_1, e_cap)]
    votes0 = (
        (law_1 >= seqw_i[None, :]) & valid_1[:, None] & valid_i[None, :]
    ).astype(F32)
    return votes0, famous_i, valid_i


def fame_vote_math(
    cfg: DagConfig, state: DagState, i, d, votes, famous_i, valid_i,
    batch_window: bool,
):
    """One voting step at distance d for round i (shared between the
    fused blockwise form and ops/wide.py's host-driven loop): round
    i+d's witnesses tally round i+d-1's votes on round i's witnesses.
    Returns (votes', famous_i')."""
    sm, e_cap = cfg.super_majority, cfg.e_cap
    jl = i + d                      # window row of voting round j
    ws_j = _wrow(state.wslot, jl)
    valid_j = ws_j >= 0
    wsx_j = sanitize(ws_j, e_cap)
    law_j = state.la[wsx_j]
    ws_p = _wrow(state.wslot, jl - 1)
    valid_p = ws_p >= 0
    fdw_p = state.fd[sanitize(ws_p, e_cap)]

    cnt = ss_counts(law_j, fdw_p, cfg.s_cap, batch_window)
    ss = (
        (cnt >= sm) & valid_j[:, None] & valid_p[None, :]
    ).astype(F32)
    tot = ss.sum(-1)                                    # [N]
    yays = jax.lax.dot_general(
        ss.astype(BF16), votes.astype(BF16),
        (((1,), (0,)), ((), ())), preferred_element_type=F32,
    )                                                   # [N_y, N_x]
    nays = tot[:, None] - yays
    v = yays >= nays
    t = jnp.maximum(yays, nays)
    strong = t >= sm
    normal = (d % cfg.active_n) != 0

    deciding = strong & normal
    decide_x = deciding.any(axis=0)                     # over voters
    v_star = (deciding & v).any(axis=0)
    und = (famous_i == FAME_UNDEFINED) & valid_i
    famous_i = jnp.where(
        und & decide_x,
        jnp.where(v_star, FAME_TRUE, FAME_FALSE).astype(jnp.int8),
        famous_i,
    )

    mb_j = state.mbit[wsx_j]
    coin_vote = jnp.where(strong, v, mb_j[:, None])
    votes = jnp.where(normal, v, coin_vote).astype(F32)
    return votes, famous_i


def fame_advance_lcr(cfg: DagConfig, state: DagState, famous_out,
                     gate: bool = False):
    """Advance last consensus round: highest window round with all
    witnesses decided (matching the reference's ascending
    set-on-each-decided-i loop, hashgraph.go:654-673)."""
    R = cfg.r_cap
    wsl = state.wslot[:R]
    valid_w = wsl >= 0
    i_idx = jnp.arange(R, dtype=I32) + state.r_off
    in_window = (i_idx > state.lcr) & (i_idx < state.max_round)
    if gate:
        in_window = in_window & (i_idx <= head_round_min_math(cfg, state))
    decided_round = (
        (~valid_w) | (famous_out[:R] != FAME_UNDEFINED)
    ).all(axis=1)
    has_w = valid_w.any(axis=1)
    cand = _lcr_candidates(
        state, i_idx, in_window, decided_round, has_w, gate
    )
    new_lcr = jnp.max(jnp.where(cand, i_idx, -1))
    return jnp.maximum(state.lcr, new_lcr)


def _wrow(tab, r_loc):
    return jax.lax.dynamic_slice_in_dim(tab, r_loc, 1, 0)[0]


def decide_fame_auto_impl(
    cfg: DagConfig, state: DagState, batch_window: bool = True,
    gate: bool = False,
) -> DagState:
    """Static shape-based dispatch between the two DecideFame forms."""
    if fame_mode(cfg) == "block":
        return decide_fame_block_impl(cfg, state, batch_window, gate)
    return decide_fame_impl(cfg, state, gate)


# Rolled-window-safe jitted form for the live engine: blockwise fame past
# the working-set bound, with the absolute-seq compare path (one-hot needs
# the fresh-state window invariant the live engine can't promise).
decide_fame_auto = jax.jit(
    decide_fame_auto_impl, static_argnums=(0, 2, 3), donate_argnums=(1,)
)
