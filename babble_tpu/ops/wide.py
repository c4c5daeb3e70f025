"""Host-orchestrated, column-blocked consensus pipeline for wide
participant axes.

Why this exists — four XLA:TPU memory behaviors, all measured as real
OOMs on one 16 GB v5e at the 10k-participant configs (VERDICT r2
missing #1):

1. A gather operand inside ANY device loop (while/scan/fori) gets a
   layout-transposed copy of the WHOLE operand when it is loop-invariant
   (hoisting turns an unchanged carry back into an invariant).
2. Even a straight-line gather pays a one-operand-sized relayout temp.
3. A donated argument that merely passes through a program costs a
   flaky full-size copy; gather+scatter of one donated operand in one
   program copy-protects it (XLA cannot prove disjointness).
4. Multi-GB scan carries are double-buffered.

The la/fd coordinate tensors are [E+1, N] — 4.5 GB each at 10k x 450k
even in int8 — so "one operand" is most of the chip.  The fix with
teeth: **store them column-blocked**, as C separate arrays of shape
[E+1, ceil(N/C)].  Every consensus reduction is independent or
accumulative across the participant axis, so each program touches one
block and every hidden copy is bounded by ~coord_bytes/C:

- la/fd level scans: column-independent recurrences — one fused
  lax.scan program per block (double-buffer = one block).
- strongly-see counts (frontier march, fame voting): per-block partial
  counts accumulated into an [N, N] i32 tally (sum over chain blocks —
  exactly the psum-over-"p" decomposition of parallel/sharded.py, with
  blocks standing in for shards on a single chip).
- round-received / median timestamps: per-block partial see-counts and
  per-block timestamp columns, concatenated only at [chunk, N] size.

Loops live on the host (step programs + host loop, like a training
loop); loop-control scalars sync once per step, and the loops throttle
every few dispatches because enqueued programs allocate their outputs
at dispatch time.

Bit-parity with the fused single-jit pipeline is pinned by
tests/test_wide.py at small shapes with forced blocking.

Rolling-window support (VERDICT r3 item 5; ops/stream.py is the driver):
the blocked la/fd store **window-local** seq values — ``abs_seq -
s_off[col]`` — with a floor clamp at -1.  On fresh states (offsets zero)
this is bit-identical to the old absolute convention, so every fresh-
state parity test still pins the same tensors.  Under compaction:

- la: any value < 0 means "no ancestor on this chain at or above the
  window base".  The two un-windowed cases (no ancestor at all vs an
  ancestor that rolled off) compare identically against every in-window
  threshold, so one sentinel (-1) serves both.
- fd: INF keeps "no descendant"; -1 means "first descendant below the
  window base" — which still compares exactly in every consumer: the
  strongly-see right side only ever gathers witness rows of live rounds
  (their descendants have rounds >= r_off and therefore live in the
  window — proven in ops/stream.py), and the order phase's
  ``fd <= seq_w`` is exactly true for any below-window descendant.
- one comparison family would be inexact — la vs fd when BOTH sides are
  below-window — and it provably never occurs on witness rows; the
  median kernel additionally reports a ``bad`` row count (below-window
  fd selected by a newly-ordered row) that the stream driver asserts 0.

All chain positions the march/fame/order kernels exchange (pos tables,
bisect bounds, witness seqs) are window-local as well; compaction shifts
block rows and rebases values per column in one gather+select program.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import fame as fame_ops
from . import ingest as ingest_ops
from . import order as order_ops
from .ingest import EventBatch
from .ss import ss_counts_compare, ss_counts_onehot
from .state import (
    DagConfig,
    DagState,
    I32,
    init_state,
    sanitize,
    set_sentinel,
)

INT64_MAX = jnp.iinfo(jnp.int64).max

# target bytes per coordinate block; a gather relayout temp is bounded
# by this, so keep it well under the post-residency headroom
BLOCK_TARGET_BYTES = 1 << 30


def wide_wins(cfg: DagConfig) -> bool:
    """Same working-set bound as ops.fame.fame_mode."""
    return fame_ops.fame_mode(cfg) == "block"


def block_count(cfg: DagConfig) -> int:
    bytes_per = (cfg.e_cap + 1) * cfg.n * np.dtype(cfg.coord_dtype).itemsize
    return max(1, -(-bytes_per // BLOCK_TARGET_BYTES))


def _block_width(cfg: DagConfig, C: int) -> int:
    return -(-cfg.n // C)


def _use_onehot_partial(cfg: DagConfig) -> bool:
    """Per-block strongly-see partial: int8 one-hot MXU vs VPU compare.
    The one-hot pays an (s_cap+1)-fold flop redundancy but runs ~570x
    faster (394 int8 Tops vs the measured 0.69 Tops XLA compare-reduce),
    so it wins until chains get very deep.  Measured at N=10k: 0.47 s vs
    1.44 s at S=32; 2.2x at S=93."""
    return (jax.default_backend() == "tpu" and cfg.n >= 4096
            and cfg.s_cap <= 512)


@functools.lru_cache(maxsize=8)
def _jits(cfg: DagConfig, C: int):
    """Per-(config, block-count) jitted step programs."""
    n, e_cap, s_cap, r_cap = cfg.n, cfg.e_cap, cfg.s_cap, cfg.r_cap
    w = _block_width(cfg, C)
    sm = cfg.super_majority
    cd = cfg.coord_dtype
    e_row = jnp.arange(e_cap + 1) == e_cap

    # ---------------- coords ----------------

    def _write_batch(state, batch):
        # la/fd are block arrays, never part of `state` here
        return ingest_ops._write_batch_fields(state, cfg, batch)

    write_batch = jax.jit(_write_batch, donate_argnums=(0,))

    def _la_block_scan(sp, op, creator, seq, s_off, la_blk, slot_sched,
                       blk_off):
        """Whole-schedule la fill for one column block (fused scan; the
        double-buffered carry is one block).  Own-seq writes are
        window-local (module docstring)."""
        col = jnp.arange(w)

        def step(la, idx):
            spx = sanitize(sp[idx], e_cap)
            opx = sanitize(op[idx], e_cap)
            rows = jnp.maximum(la[spx], la[opx])             # [B, w]
            own = creator[idx] - blk_off                     # block-local col
            own_here = (own >= 0) & (own < w)
            seq_loc = seq[idx] - s_off[jnp.clip(creator[idx], 0, n)]
            rows = jnp.where(
                own_here[:, None] & (col[None, :] == own[:, None]),
                seq_loc[:, None].astype(rows.dtype), rows,
            )
            return la.at[idx].set(rows), None

        la_blk, _ = jax.lax.scan(step, la_blk, slot_sched)
        return set_sentinel(la_blk, e_row[:, None], -1)

    la_block_scan = jax.jit(_la_block_scan, donate_argnums=(5,))

    def _fd_block_scan(sp, op, creator, seq, s_off, b_seq, b_k, n_events,
                       fd_blk, slot_sched, blk_off):
        """Whole-schedule reversed fd fill for one column block,
        including the own-seq seeding (_fd_init_own's block slice;
        window-local values)."""
        kpad = b_seq.shape[0]
        pos = jnp.arange(kpad, dtype=I32)
        real = pos < b_k
        slots = jnp.where(real, n_events - b_k + pos, e_cap)
        own_c = jnp.where(real, creator[slots], n)
        own = own_c - blk_off
        own_here = (own >= 0) & (own < w) & real
        b_seq_loc = b_seq - s_off[jnp.clip(own_c, 0, n)]
        fd_blk = fd_blk.at[
            jnp.where(own_here, slots, e_cap),
            jnp.clip(own, 0, w - 1),
        ].set(b_seq_loc.astype(fd_blk.dtype))

        def step(fd, idx):
            rows = fd[idx]                                   # [B, w]
            spx = sanitize(sp[idx], e_cap)
            opx = sanitize(op[idx], e_cap)
            fd = fd.at[spx].min(rows)
            return fd.at[opx].min(rows), None

        fd_blk, _ = jax.lax.scan(step, fd_blk, slot_sched[::-1])
        return set_sentinel(fd_blk, e_row[:, None], cfg.fd_inf)

    fd_block_scan = jax.jit(_fd_block_scan, donate_argnums=(8,))

    def _coord_sent(state):
        return ingest_ops._reset_coord_sentinels(
            state, cfg, include_coords=False
        )

    coord_sent = jax.jit(_coord_sent, donate_argnums=(0,))

    # ---------------- blocked strongly-see partials ----------------

    # one-hot band compression (ss.py module docstring): witness fd
    # values cluster within ~1-2 rounds of each chain's frontier, so a
    # per-column offset + a small static band cuts the matmul's
    # S1-fold flop redundancy ~2-3x at deep windows.  The band check is
    # a lax.cond: out-of-band calls fall back to the full-range matmul.
    SS_BAND = 48

    def _ss_partial(rows_a, rows_b, acc):
        """acc += |{k in block : rows_a[a,k] >= rows_b[b,k]}| — exact
        per-block partial of the strongly-see count (rows_b are witness
        fd rows: finite values are in-window by the stream eviction
        proof, so the one-hot bucket range is [0, s_cap])."""
        if not _use_onehot_partial(cfg):
            return acc + ss_counts_compare(rows_a, rows_b)
        if s_cap <= SS_BAND * 2:
            return acc + ss_counts_onehot(rows_a, rows_b, s_cap)
        inf = int(cfg.fd_inf)
        finite = (rows_b >= 0) & (rows_b < inf)
        col_min = jnp.min(
            jnp.where(finite, rows_b.astype(I32), jnp.iinfo(I32).max),
            axis=0,
        )
        off = jnp.where(col_min == jnp.iinfo(I32).max, 0, col_min)
        in_band = jnp.where(
            finite, rows_b.astype(I32) - off[None, :], 0
        ) <= SS_BAND
        part = jax.lax.cond(
            in_band.all(),
            lambda: ss_counts_onehot(rows_a, rows_b, SS_BAND,
                                     off=off.astype(rows_b.dtype)),
            lambda: ss_counts_onehot(rows_a, rows_b, s_cap),
        )
        return acc + part

    ss_partial = jax.jit(_ss_partial, donate_argnums=(2,))

    def _gather_rows(blk, idx):
        """[A, w] rows of one coordinate block (sentinel row for idx<0)."""
        return blk[sanitize(idx, e_cap)]

    gather_rows = jax.jit(_gather_rows)

    # ---------------- frontier march ----------------

    def _frontier_prep(state):
        cnt = state.cnt[:n] - state.s_off[:n]
        pos0 = jnp.where(cnt > 0, 0, jnp.iinfo(I32).max)
        pos_table0 = jnp.full((r_cap + 1, n), jnp.iinfo(I32).max, I32)
        pos_table0 = pos_table0.at[0].set(pos0)
        return cnt, pos0, pos_table0

    frontier_prep = jax.jit(_frontier_prep)

    def _round_witnesses(state, cnt, pos):
        valid_w = pos < cnt
        ws = state.ce[:n][jnp.arange(n), jnp.clip(pos, 0, s_cap)]
        return jnp.where(valid_w, ws, -1), valid_w

    round_witnesses = jax.jit(_round_witnesses)

    def _bisect_candidates(state, lo, hi):
        mid = (lo + hi) >> 1
        xs = state.ce[:n][jnp.arange(n), jnp.clip(mid, 0, s_cap)]
        return mid, xs

    bisect_candidates = jax.jit(_bisect_candidates)

    def _bisect_update(cnt_ab, valid_w, lo, hi, mid, chains_cnt):
        ss = (cnt_ab >= sm) & valid_w[None, :]
        ok = ss.sum(-1) >= sm
        active = lo < hi
        hi = jnp.where(ok & active, mid, hi)
        lo = jnp.where(~ok & active, mid + 1, lo)
        return lo, hi

    bisect_update = jax.jit(_bisect_update)

    def _col_gather(v, blk_off, fill=None):
        """Block-columns of a length-n vector via clipped gather — a
        dynamic_slice would clamp its start on the ragged last block and
        misalign every column."""
        cols = blk_off + jnp.arange(w)
        out = v[jnp.clip(cols, 0, v.shape[0] - 1)]
        if fill is not None:
            out = jnp.where(cols < n, out, fill)
        return out

    def _inherit_block(fde_blk):
        """Per-block descent inheritance: min over witnesses of their
        first-inc events' fd rows (already window-local positions)."""
        m = fde_blk.min(axis=0).astype(I32)                  # [w] local
        return jnp.where(m >= int(cfg.fd_inf), jnp.iinfo(I32).max, m)

    inherit_block = jax.jit(_inherit_block)

    def _frontier_next(cnt, pos, pos_table, r, s_star, found, inherit,
                       frozen, prev_next):
        pos_next = jnp.minimum(
            jnp.where(found, s_star, jnp.iinfo(I32).max), inherit
        )
        pos_next = jnp.maximum(pos_next, pos)  # monotone safety
        # resumed march: positions found at an earlier march are frozen
        # (old events' round criteria are append-invariant — stream.py)
        pos_next = jnp.where(frozen, prev_next, pos_next)
        any_next = (pos_next < cnt).any()
        pos_table = pos_table.at[jnp.minimum(r + 1, r_cap)].set(pos_next)
        return pos_next, pos_table, any_next

    frontier_next = jax.jit(_frontier_next, donate_argnums=(2,))

    def _march_bounds(pos_r, prev_next, cnt, cnt_prev):
        """Bisect bounds for one resumed march step: frozen chains pin
        lo=hi at their known position; open chains search only the
        events appended since the last march (window-local).  On fresh
        runs (cnt_prev=0) this degenerates to the original full-range
        bounds bit-exactly."""
        frozen = prev_next < cnt_prev
        valid_w = pos_r < cnt
        lo_u = jnp.where(valid_w, jnp.maximum(pos_r, cnt_prev), cnt)
        lo = jnp.where(frozen, prev_next, lo_u)
        hi = jnp.where(frozen, prev_next, cnt)
        span = jnp.max(jnp.maximum(hi - lo, 0))
        return frozen, lo, hi, span

    march_bounds = jax.jit(_march_bounds)

    def _march_open(pos_table, cnt_prev):
        """Per-round-row openness: a row is closed once every chain's
        position was found before the last march (then no appended event
        can change it)."""
        return (pos_table >= cnt_prev[None, :]).any(axis=1)

    march_open = jax.jit(_march_open)

    def _wit_seq_loc(state_seq, state_s_off, ws):
        """Window-local witness seqs per creator column — ws is creator-
        indexed ([N] or [R, N]), so column k subtracts s_off[k].
        Sentinel rows yield negatives, masked by the callers' validity
        masks."""
        return state_seq[sanitize(ws, e_cap)] - state_s_off[:n]

    wit_seq_loc = jax.jit(_wit_seq_loc)

    def _frontier_fin(state, pos_table):
        state = ingest_ops.frontier_finalize(state, cfg, pos_table)
        return ingest_ops._reset_round_sentinels(state, cfg)

    frontier_fin = jax.jit(_frontier_fin, donate_argnums=(0,))

    # ---------------- fame ----------------

    def _wrow(tab, r_loc):
        return jax.lax.dynamic_slice_in_dim(tab, r_loc, 1, 0)[0]

    def _fame_wits(state, i):
        """Witness slots/validity for rounds i (subject), i-1 unused."""
        ws = _wrow(state.wslot, i)
        return ws, ws >= 0

    fame_wits = jax.jit(_fame_wits)

    def _head_round_min(state):
        """Smallest chain-head round over all minted chains: rounds are
        monotone along a chain, so round i's witness set is FINAL iff
        every chain's head round >= i.  Mid-stream fame gates decisions
        on this (ops/stream.py), which makes streaming scheduling-
        invariant and bit-identical to the whole-DAG batch.

        Liveness assumption (ADVICE r4 low): never-minted chains map to
        -1, so mid-stream fame (complete=False) decides nothing until
        every one of the N participants has minted at least one event —
        and a chain that stops minting forever freezes the head-round
        minimum, deferring all further decisions to the final full-DAG
        pass (unbounded live window).  This is the same all-N liveness
        the protocol itself has (a round's witness set needs every
        creator to reach it; the reference advances LastConsensusRound
        only when all witnesses of a round are decided).  A production
        stream that must survive permanently-offline participants needs
        an inactivity horizon that excludes stale chains from this
        minimum — which changes the witness universe and is a consensus-
        visible membership decision, not a local optimization; the
        stream keeps the conservative protocol semantics instead."""
        cnt_w = state.cnt[:n] - state.s_off[:n]
        heads = state.ce[jnp.arange(n), jnp.clip(cnt_w - 1, 0, s_cap)]
        hr = state.round[sanitize(jnp.where(cnt_w > 0, heads, -1), e_cap)]
        return jnp.min(jnp.where(state.cnt[:n] > 0, hr, -1))

    head_round_min = jax.jit(_head_round_min)

    def _votes0_block(la1_blk_rows, seqw_i, blk_off, valid_1, valid_i):
        """Block-columns of the d=1 direct see votes."""
        sw = _col_gather(seqw_i, blk_off)
        vi = _col_gather(valid_i, blk_off, fill=False)
        return (
            (la1_blk_rows >= sw[None, :])
            & valid_1[:, None] & vi[None, :]
        ).astype(jnp.float32)

    votes0_block = jax.jit(_votes0_block)

    def _fame_tally(cnt_ab, valid_j, valid_p, valid_i, votes, famous_i,
                    mb_j, d):
        ss = ((cnt_ab >= sm) & valid_j[:, None] & valid_p[None, :]
              ).astype(jnp.float32)
        tot = ss.sum(-1)
        yays = jax.lax.dot_general(
            ss.astype(jnp.bfloat16), votes.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        nays = tot[:, None] - yays
        v = yays >= nays
        strong = jnp.maximum(yays, nays) >= sm
        normal = (d % cfg.active_n) != 0

        deciding = strong & normal
        decide_x = deciding.any(axis=0)
        v_star = (deciding & v).any(axis=0)
        und = (famous_i == fame_ops.FAME_UNDEFINED) & valid_i
        famous_i = jnp.where(
            und & decide_x,
            jnp.where(v_star, fame_ops.FAME_TRUE,
                      fame_ops.FAME_FALSE).astype(jnp.int8),
            famous_i,
        )
        coin_vote = jnp.where(strong, v, mb_j[:, None])
        votes = jnp.where(normal, v, coin_vote).astype(jnp.float32)
        und2 = (famous_i == fame_ops.FAME_UNDEFINED) & valid_i
        return votes, famous_i, und2.any()

    fame_tally = jax.jit(_fame_tally, donate_argnums=(4,))

    def _fame_write(famous_tab, famous_i, i):
        return jax.lax.dynamic_update_slice_in_dim(
            famous_tab, famous_i[None, :], i, 0
        )

    fame_write = jax.jit(_fame_write)

    def _fame_fin(state, famous_out):
        return fame_ops.fame_advance_lcr(cfg, state, famous_out)

    fame_fin = jax.jit(_fame_fin)

    # ---------------- order ----------------

    def _order_prep(state):
        R = r_cap
        wsl = state.wslot[:R]
        valid_w = wsl >= 0
        # window-local witness seqs (fd block values are local too)
        seqw = state.seq[sanitize(wsl, e_cap)] - state.s_off[None, :n]
        fam = (state.famous[:R] == fame_ops.FAME_TRUE) & valid_w
        decided = (
            (~valid_w) | (state.famous[:R] != fame_ops.FAME_UNDEFINED)
        ).all(axis=1)
        has_w = valid_w.any(axis=1)
        fam_cnt = fam.sum(axis=1)
        und = order_ops.order_undetermined(cfg, state)
        return seqw, fam, decided, has_w, fam_cnt, und

    order_prep = jax.jit(_order_prep)

    def _sees_partial_block(fd_blk, seqw_i, fam_i, blk_off, acc):
        """acc += per-event count of famous round-i witnesses in this
        block that see the event (streaming elementwise, no gathers)."""
        sw = _col_gather(seqw_i, blk_off)
        fm = _col_gather(fam_i, blk_off, fill=False)
        sees = fm[None, :] & (fd_blk <= sw[None, :])         # [E+1, w]
        return acc + sees.sum(axis=1, dtype=I32)

    sees_partial_block = jax.jit(_sees_partial_block, donate_argnums=(4,))

    def _order_rr_update(state, und, decided_i, has_w_i, fam_cnt_i, i,
                         c, rr):
        i_abs = i + state.r_off
        active = decided_i & has_w_i & (i_abs <= state.max_round)
        cond = (
            und & (rr == -1) & (i_abs > state.round) & active
            & (c > fam_cnt_i // 2)
        )
        return jnp.where(cond, i_abs, rr)

    order_rr_update = jax.jit(_order_rr_update)

    med_chunk = max(1, min(order_ops.MEDIAN_CHUNK_ELEMS // n,
                           cfg.e_cap + 1))

    def _col_gather_t(tab, blk_off, fill=None):
        """Block-columns of an [R, n] table (clipped gather, see
        _col_gather)."""
        cols = blk_off + jnp.arange(w)
        out = tab[:, jnp.clip(cols, 0, tab.shape[1] - 1)]
        if fill is not None:
            out = jnp.where(cols[None, :] < n, out, fill)
        return out

    def _ts_range(state):
        valid = state.seq >= 0
        tmin = jnp.min(jnp.where(valid, state.ts, INT64_MAX))
        tmax = jnp.max(jnp.where(valid, state.ts, -INT64_MAX - 1))
        # real-world timestamps are granular (the sim quantizes to 1 us);
        # dividing by the granularity is what brings a multi-hour span
        # under 2^31 for the i32 median path
        div1000 = jnp.all(
            jnp.where(valid, (state.ts - tmin) % 1000, 0) == 0
        )
        return tmin, tmax, div1000

    ts_range = jax.jit(_ts_range)

    def _med_tv_block(state, fd_blk_rows, i_rows, seqw, fam, blk_off,
                      tmin, scale, rel32):
        """Per-block tv columns for a chunk of events: the timestamp of
        chain j's event at seq fd[x, j], masked to famous seers.

        ``rel32`` (static): timestamps span < 2^31 ns, so the median
        machinery runs on i32 offsets from tmin — the S-step
        select-accumulate and the sort are this phase's HBM-bound bulk
        (measured 62% of peak bandwidth at 10k x 600k), and halving the
        element width halves it.  Rows with no seers surface INF and are
        masked by `newly` downstream (a received event always has
        seers)."""
        rows_c = jnp.clip(blk_off + jnp.arange(w), 0, n)
        cej = state.ce[rows_c]                               # [w, S+1]
        ts_grid = state.ts[sanitize(cej, e_cap)]             # i64[w, S+1]
        inf = jnp.asarray(
            jnp.iinfo(jnp.int32).max if rel32 else INT64_MAX,
            jnp.int32 if rel32 else state.ts.dtype,
        )
        if rel32:
            # invalid grid cells wrap to garbage, but every cell a `sees`
            # row selects is a real event (fd <= seqw implies existence)
            ts_grid = ((ts_grid - tmin) // scale).astype(jnp.int32)
        sw = _col_gather_t(seqw, blk_off)[i_rows]            # [chunk, w]
        fm = _col_gather_t(fam, blk_off, fill=False)[i_rows]
        sees = fm & (fd_blk_rows <= sw)
        # below-window fd selected by a seer: the ts grid can't resolve
        # it (the event rolled off) — counted and asserted 0 upstream
        # for newly-ordered rows (module docstring)
        bad = (sees & (fd_blk_rows < 0)).any(axis=1)
        fdc = jnp.clip(fd_blk_rows, 0, s_cap)
        if jax.default_backend() == "tpu" and s_cap < 2048:
            def acc_step(s, acc):
                return jnp.where(fdc == s, ts_grid[:, s][None, :], acc)

            tv = jax.lax.fori_loop(
                0, s_cap + 1, acc_step,
                jnp.full(fdc.shape, inf, dtype=ts_grid.dtype),
            )
        else:
            tv = ts_grid[jnp.arange(w)[None, :], fdc]
        return jnp.where(sees, tv, inf), sees.sum(axis=1, dtype=I32), bad

    med_tv_block = jax.jit(_med_tv_block, static_argnums=(8,))

    def _med_reduce(tv_full, cnt_s, newly_rows, cts_rows, tmin, scale,
                    rel32):
        tv_sorted = jnp.sort(tv_full, axis=1)
        rows = tv_full.shape[0]
        med = tv_sorted[jnp.arange(rows),
                        jnp.clip(cnt_s // 2, 0, n - 1)]
        if rel32:
            med = med.astype(jnp.int64) * scale + tmin
        return jnp.where(newly_rows, med, cts_rows)

    med_reduce = jax.jit(_med_reduce, static_argnums=(6,))

    def _slice_rows(a, e0, rows):
        return jax.lax.dynamic_slice_in_dim(a, e0, rows, 0)

    slice_rows = jax.jit(_slice_rows, static_argnums=(2,))

    def _write_rows(a, e0, rows):
        return jax.lax.dynamic_update_slice_in_dim(a, rows, e0, 0)

    write_rows = jax.jit(_write_rows)

    # ---------------- rolling-window compaction ----------------

    def _compact_block(blk, de, ds_cols, is_fd):
        """Shift a coordinate block down by de rows (tail back-fills from
        the sentinel row, like state.compact_impl) and rebase values to
        the new window base: local -= ds, floored at -1 ("below
        window").  la negatives and fd INF are fixpoints."""
        eidx = jnp.minimum(jnp.arange(e_cap + 1) + de, e_cap)
        v = blk[eidx]
        shifted = jnp.maximum(v.astype(I32) - ds_cols[None, :], -1)
        if is_fd:
            keep = v.astype(I32) >= int(cfg.fd_inf)
        else:
            keep = v < 0
        return jnp.where(keep, v, shifted.astype(v.dtype))

    compact_block = jax.jit(_compact_block, static_argnums=(3,),
                            donate_argnums=(0,))

    def _compact_march(pos_table, cnt_prev, dr, ds):
        """Roll the march carry: round rows shift by dr (row r_cap is
        never written by the march, so the clamp back-fills INF), and
        window-local positions rebase by each chain's seq shift."""
        inf = jnp.iinfo(I32).max
        ridx = jnp.minimum(jnp.arange(r_cap + 1) + dr, r_cap)
        pt = pos_table[ridx]
        pt = jnp.where(pt == inf, inf, jnp.maximum(pt - ds[None, :], 0))
        return pt, jnp.maximum(cnt_prev - ds, 0)

    compact_march = jax.jit(_compact_march, donate_argnums=(0,))

    def _newly_range(newly):
        """[lo, hi) slot bounds of the newly-ordered rows (the median
        only needs to stream those; INT32_MAX/-1 when empty)."""
        idx = jnp.arange(newly.shape[0])
        inf = jnp.iinfo(I32).max
        lo = jnp.min(jnp.where(newly, idx, inf))
        hi = jnp.max(jnp.where(newly, idx, -1)) + 1
        return lo, hi

    newly_range = jax.jit(_newly_range)

    # ---------------- stacked twins (sharded streaming) ----------------
    # The same block kernels vmapped over a leading block axis
    # [C, E+1, w]: one jitted program per phase step instead of C host
    # dispatches, and — with the stacked blocks spread over every device
    # of a mesh (block_sharding) — XLA
    # partitions each vmapped kernel per-device and turns the
    # cross-block reductions (.sum(0) / .any(0) / reshape-concat) into
    # ICI collectives.  ``offs`` is the per-block column origin,
    # jnp.arange(C) * w.  Bit-parity with the tuple path is pinned by
    # tests/test_stream.py and tests/test_parallel.py.

    la_scan_stacked = jax.jit(
        jax.vmap(_la_block_scan, in_axes=(None,) * 5 + (0, None, 0)),
        donate_argnums=(5,),
    )
    fd_scan_stacked = jax.jit(
        jax.vmap(_fd_block_scan, in_axes=(None,) * 8 + (0, None, 0)),
        donate_argnums=(8,),
    )
    gather_stacked = jax.jit(jax.vmap(_gather_rows, in_axes=(0, None)))

    def _ss_stacked(law, fdw):
        z = jnp.zeros((law.shape[1], fdw.shape[1]), I32)
        return jax.vmap(
            lambda a, b: _ss_partial(a, b, z)
        )(law, fdw).sum(0)

    ss_stacked = jax.jit(_ss_stacked)

    def _votes0_stacked(law, seqw_i, offs, valid_1, valid_i):
        v = jax.vmap(_votes0_block, in_axes=(0, None, 0, None, None))(
            law, seqw_i, offs, valid_1, valid_i
        )
        return jnp.swapaxes(v, 0, 1).reshape(v.shape[1], -1)[:, :n]

    votes0_stacked = jax.jit(_votes0_stacked)

    def _inherit_stacked(fde):
        return jax.vmap(_inherit_block)(fde).reshape(-1)[:n]

    inherit_stacked = jax.jit(_inherit_stacked)

    def _sees_stacked(FD, seqw_i, fam_i, offs):
        z = jnp.zeros((e_cap + 1,), I32)
        return jax.vmap(
            lambda blk, o: _sees_partial_block(blk, seqw_i, fam_i, o, z)
        )(FD, offs).sum(0)

    sees_stacked = jax.jit(_sees_stacked)

    def _med_tv_stacked(state, FD_rows, i_rows, seqw, fam, offs, tmin,
                        scale, rel32):
        tv, cnt, bad = jax.vmap(
            _med_tv_block,
            in_axes=(None, 0, None, None, None, 0, None, None, None),
        )(state, FD_rows, i_rows, seqw, fam, offs, tmin, scale, rel32)
        tvf = jnp.swapaxes(tv, 0, 1).reshape(tv.shape[1], -1)[:, :n]
        return tvf, cnt.sum(0), bad.any(0)

    med_tv_stacked = jax.jit(_med_tv_stacked, static_argnums=(8,))

    def _slice_stacked(A, e0, rows):
        return jax.lax.dynamic_slice_in_dim(A, e0, rows, 1)

    slice_stacked = jax.jit(_slice_stacked, static_argnums=(2,))

    compact_stacked = jax.jit(
        jax.vmap(_compact_block, in_axes=(0, None, 0, None)),
        static_argnums=(3,), donate_argnums=(0,),
    )

    return dict(
        write_batch=write_batch, la_block_scan=la_block_scan,
        fd_block_scan=fd_block_scan, coord_sent=coord_sent,
        ss_partial=ss_partial, gather_rows=gather_rows,
        frontier_prep=frontier_prep, round_witnesses=round_witnesses,
        bisect_candidates=bisect_candidates, bisect_update=bisect_update,
        inherit_block=inherit_block, frontier_next=frontier_next,
        march_bounds=march_bounds, march_open=march_open,
        wit_seq_loc=wit_seq_loc,
        frontier_fin=frontier_fin,
        fame_wits=fame_wits, head_round_min=head_round_min,
        votes0_block=votes0_block,
        fame_tally=fame_tally, fame_write=fame_write, fame_fin=fame_fin,
        order_prep=order_prep, sees_partial_block=sees_partial_block,
        order_rr_update=order_rr_update, med_tv_block=med_tv_block,
        ts_range=ts_range,
        med_reduce=med_reduce, slice_rows=slice_rows,
        write_rows=write_rows, med_chunk=med_chunk, width=w,
        compact_block=compact_block, compact_march=compact_march,
        newly_range=newly_range,
        la_scan_stacked=la_scan_stacked, fd_scan_stacked=fd_scan_stacked,
        gather_stacked=gather_stacked, ss_stacked=ss_stacked,
        votes0_stacked=votes0_stacked, inherit_stacked=inherit_stacked,
        sees_stacked=sees_stacked, med_tv_stacked=med_tv_stacked,
        slice_stacked=slice_stacked, compact_stacked=compact_stacked,
    )


class MarchCarry:
    """Persistent frontier-march state for windowed streaming
    (ops/stream.py): the per-round first-position table plus the chain
    lengths at the last march (what freezes already-found positions)."""

    __slots__ = ("pos_table", "cnt_prev")

    def __init__(self, pos_table, cnt_prev):
        self.pos_table = pos_table
        self.cnt_prev = cnt_prev


def _init_blocks(cfg: DagConfig, C: int):
    w = _block_width(cfg, C)
    e1 = cfg.e_cap + 1
    la = tuple(jnp.full((e1, w), -1, cfg.coord_dtype) for _ in range(C))
    fd = tuple(
        jnp.full((e1, w), cfg.fd_inf, cfg.coord_dtype) for _ in range(C)
    )
    return la, fd


def block_sharding(mesh):
    """The stacked blocks' layout on ``mesh``: the block axis spread over
    EVERY mesh axis, so each device owns C / mesh.size blocks.  Sharding
    over "p" alone would hold each block once per "ev" device: on the 2x2
    mesh ``make_mesh(4)`` builds, the 10k strongly-see tally then needs
    18.33 GB of a chip's 15.75 GB (described-chip compile, PR 21)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return NamedSharding(mesh, P(tuple(mesh.axis_names), None, None))


def _init_blocks_stacked(cfg: DagConfig, C: int, mesh=None):
    """Stacked block arrays [C, E+1, w]; with ``mesh`` they are laid out
    by ``block_sharding`` and the stacked kernels run SPMD with
    XLA-inserted collectives."""
    w = _block_width(cfg, C)
    e1 = cfg.e_cap + 1

    def build():
        return (jnp.full((C, e1, w), -1, cfg.coord_dtype),
                jnp.full((C, e1, w), cfg.fd_inf, cfg.coord_dtype))

    if mesh is None:
        return build()
    if C % mesh.size:
        raise ValueError(
            f"block count C={C} must be a multiple of the mesh's "
            f"{mesh.size} devices"
        )
    sh = block_sharding(mesh)
    # filled in place, shard by shard: building the whole window on the
    # default device first does not fit there at 10k (a 4-chip run ran
    # out of device 0's memory placing it, PR 21)
    return jax.jit(build, out_shardings=(sh, sh))()


def _is_stacked(blocks) -> bool:
    return not isinstance(blocks, (tuple, list))


def _block_offs(C: int, w: int):
    return jnp.arange(C, dtype=I32) * w


def _gather_all(j, C, blocks, idx):
    """Rows of every block for slot indices idx: stacked [C, A, w] or a
    list of C [A, w] arrays."""
    if _is_stacked(blocks):
        return j["gather_stacked"](blocks, idx)
    return [j["gather_rows"](blocks[c], idx) for c in range(C)]


def _ss_all(j, C, w, law, fdw, n):
    """Full strongly-see counts from per-block gathered rows."""
    if _is_stacked(law):
        return j["ss_stacked"](law, fdw)
    return _blocked_ss(j, C, w, law, fdw, n)


def _split_blocks(cfg: DagConfig, C: int, full: jnp.ndarray, fill):
    """Split a full [E+1, N] tensor into C padded column blocks."""
    w = _block_width(cfg, C)
    e1 = cfg.e_cap + 1
    out = []
    for c in range(C):
        blk = full[:, c * w : (c + 1) * w]
        if blk.shape[1] < w:
            blk = jnp.concatenate(
                [blk, jnp.full((e1, w - blk.shape[1]), fill, blk.dtype)],
                axis=1,
            )
        out.append(blk)
    return tuple(out)


def _assemble_blocks(cfg: DagConfig, blocks) -> jnp.ndarray:
    return jnp.concatenate(blocks, axis=1)[:, : cfg.n]


def run_wide_coords(cfg: DagConfig, state: DagState, batch: EventBatch,
                    la_blocks, fd_blocks, C: int, fd_slot_sched=None):
    """Blocked coordinate fill: batch write + per-block la/fd scans
    (window-local values; exact on fresh states where offsets are 0).

    ``fd_slot_sched`` (streaming): a level schedule of WINDOW slots the
    reversed fd sweep must cover.  An la row is final at insert (an
    event's ancestors are fixed), so la scans only the batch — but fd
    rows keep gaining first-descendants until every chain has one, and
    a batch-only reverse scan would propagate new descendants just one
    hop into pre-batch history (observed as a stalled frontier march:
    round-r witnesses never learned of their next-batch descendants
    through pre-batch intermediaries).  Min is idempotent and rows
    never forget, so re-sweeping all live levels reaches the exact
    transitive closure.  Default (one-shot batch): the batch schedule
    IS the whole window.

    Why coords runs far from the rooflines (r3 measured 2% of peak at
    10k — VERDICT r4 item 5): the la/fd fills are lax.scans over T
    topological levels, and each step's work is two gathered row-sets
    of [B, w] coordinates — a few MB of HBM traffic against a fixed
    per-step scan overhead, with a strict sequential dependence
    between levels (a child's row is the max/min of its parents'
    finished rows).  The phase is therefore LATENCY-bound by
    T x step-overhead, not bandwidth- or compute-bound, and no
    roofline axis applies; the knobs that move it are fewer programs
    (the stacked path replaces C per-block dispatches with one
    vmapped scan), fewer levels per program (bigger stream batches
    amortize the fixed cost), and wider rows (larger B per level).
    A Pallas kernel cannot remove the level-sequential dependence —
    it is the DAG's own depth."""
    j = _jits(cfg, C)
    state = j["write_batch"](state, batch)
    base = state.n_events - batch.k
    slot_sched = jnp.where(
        batch.sched >= 0, base + batch.sched, cfg.e_cap
    )
    if fd_slot_sched is None:
        fd_slot_sched = slot_sched
    w = j["width"]
    sp, op, creator, seq = state.sp, state.op, state.creator, state.seq
    s_off = state.s_off
    if _is_stacked(la_blocks):
        offs = _block_offs(C, w)
        la_blocks = j["la_scan_stacked"](sp, op, creator, seq, s_off,
                                         la_blocks, slot_sched, offs)
        fd_blocks = j["fd_scan_stacked"](sp, op, creator, seq, s_off,
                                         batch.seq, batch.k,
                                         state.n_events, fd_blocks,
                                         fd_slot_sched, offs)
    else:
        la_blocks = tuple(
            j["la_block_scan"](sp, op, creator, seq, s_off, la_blocks[c],
                               slot_sched, jnp.asarray(c * w, I32))
            for c in range(C)
        )
        fd_blocks = tuple(
            j["fd_block_scan"](sp, op, creator, seq, s_off, batch.seq,
                               batch.k, state.n_events, fd_blocks[c],
                               fd_slot_sched, jnp.asarray(c * w, I32))
            for c in range(C)
        )
    state = j["coord_sent"](state)
    return state, la_blocks, fd_blocks


def _blocked_ss(j, C, w, la_rows_by_block, fd_rows_by_block, n):
    """Accumulate per-block strongly-see partials into [A, B] counts."""
    acc = jnp.zeros(
        (la_rows_by_block[0].shape[0], fd_rows_by_block[0].shape[0]), I32
    )
    for c in range(C):
        acc = j["ss_partial"](la_rows_by_block[c], fd_rows_by_block[c],
                              acc)
    return acc


def run_wide_rounds(cfg: DagConfig, state: DagState, la_blocks,
                    fd_blocks, C: int, stats=None,
                    carry: Optional[MarchCarry] = None) -> DagState:
    """Blocked host-driven frontier march (device twin:
    _rounds_frontier, differentially tested).

    With ``carry`` (windowed streaming) the march resumes: rows whose
    positions were all found at the last march are frozen — appended
    events cannot change them, because an event's round criterion only
    counts ancestor witnesses (ops/stream.py "append-invariance") — and
    open rows bisect only over the appended suffix.  The carry is
    updated in place (pos_table/cnt_prev) for the next resume."""
    j = _jits(cfg, C)
    w = j["width"]
    n, s_cap, r_cap = cfg.n, cfg.s_cap, cfg.r_cap

    cnt, pos0, pos_table0 = j["frontier_prep"](state)
    if carry is None:
        pos_table = pos_table0
        cnt_prev = jnp.zeros((n,), I32)
        r = 0
    else:
        # refresh row 0 (chains empty at the last march may be live now)
        pos_table = carry.pos_table.at[0].set(pos0)
        cnt_prev = carry.cnt_prev
        open_rows = np.asarray(j["march_open"](pos_table, cnt_prev))
        first_open = int(np.argmax(open_rows)) if open_rows.any() else 0
        r = max(0, first_open - 1)
    pos = pos_table[r]

    steps = 0
    alive = True
    while alive and r < r_cap - 1:
        frozen, lo, hi, span = j["march_bounds"](
            pos, pos_table[r + 1], cnt, cnt_prev
        )
        ws, valid_w = j["round_witnesses"](state, cnt, pos)
        fdw = _gather_all(j, C, fd_blocks, ws)

        bisect_iters = max(1, int(span).bit_length())
        for _ in range(bisect_iters):
            mid, xs = j["bisect_candidates"](state, lo, hi)
            law = _gather_all(j, C, la_blocks, xs)
            cnt_ab = _ss_all(j, C, w, law, fdw, n)
            lo, hi = j["bisect_update"](cnt_ab, valid_w, lo, hi, mid,
                                        cnt)
        if stats is not None:
            stats["ss_tallies"] = stats.get("ss_tallies", 0) + bisect_iters
        s_star = lo
        found = s_star < cnt

        # descent inheritance via the first-inc events' fd rows
        _, e_star = j["bisect_candidates"](state, s_star, s_star)
        e_star = jnp.where(found, e_star, -1)
        if _is_stacked(fd_blocks):
            inherit = j["inherit_stacked"](
                j["gather_stacked"](fd_blocks, e_star)
            )
        else:
            inh = [
                j["inherit_block"](j["gather_rows"](fd_blocks[c], e_star))
                for c in range(C)
            ]
            inherit = jnp.concatenate(inh)[:n]
        pos, pos_table, any_next = j["frontier_next"](
            cnt, pos, pos_table, jnp.asarray(r, I32), s_star, found,
            inherit, frozen, pos_table[r + 1],
        )
        alive = bool(any_next)
        r += 1
        steps += 1

    if stats is not None:
        stats["round_steps"] = stats.get("round_steps", 0) + steps
        stats["bisect_iters"] = max(1, (s_cap + 1).bit_length())
    if carry is not None:
        carry.pos_table = pos_table
        carry.cnt_prev = cnt
    return j["frontier_fin"](state, pos_table)


def run_wide_fame(cfg: DagConfig, state: DagState, la_blocks, fd_blocks,
                  C: int, stats=None, complete: bool = True) -> DagState:
    """Blocked host-driven fame voting (device twin:
    decide_fame_block_impl, differentially tested).  Round indices into
    the witness/fame tables are window rows (i_abs - r_off); witness
    seqs are window-local to match the blocked coordinates.

    ``complete=False`` (mid-stream): decisions are gated to rounds
    whose witness set is provably final (every chain head's round >= i
    — _head_round_min), so a late witness can never reopen a decided
    round and the stream's output is bit-identical to the whole-DAG
    batch regardless of batch boundaries.  Fame decisions themselves
    are stable under late *voters* (the supermajority threshold is
    absolute), so gating the subject round is sufficient."""
    j = _jits(cfg, C)
    w = j["width"]
    n = cfg.n
    offs = _block_offs(C, w) if _is_stacked(la_blocks) else None
    lcr = int(state.lcr)
    max_round = int(state.max_round)
    r_off = int(state.r_off)
    hi = max_round
    if not complete:
        hi = min(hi, int(j["head_round_min"](state)) + 1)
    famous = state.famous
    for i_abs in range(max(lcr + 1, r_off), hi):
        i = i_abs - r_off
        if i >= cfg.r_cap:
            break
        ws_i, valid_i = j["fame_wits"](state, jnp.asarray(i, I32))
        seqw_i = j["wit_seq_loc"](state.seq, state.s_off, ws_i)
        famous_i = famous[i]

        ws_1, valid_1 = j["fame_wits"](state, jnp.asarray(i + 1, I32))
        if _is_stacked(la_blocks):
            votes = j["votes0_stacked"](
                j["gather_stacked"](la_blocks, ws_1), seqw_i,
                offs, valid_1, valid_i,
            )
        else:
            votes = jnp.concatenate(
                [
                    j["votes0_block"](
                        j["gather_rows"](la_blocks[c], ws_1), seqw_i,
                        jnp.asarray(c * w, I32), valid_1, valid_i,
                    )
                    for c in range(C)
                ],
                axis=1,
            )[:, :n]

        und_any = bool(((np.asarray(famous_i) == fame_ops.FAME_UNDEFINED)
                        & np.asarray(valid_i)).any())
        d = 2
        while und_any and i_abs + d <= max_round:
            ws_j, valid_j = j["fame_wits"](state,
                                           jnp.asarray(i + d, I32))
            ws_p, valid_p = j["fame_wits"](state,
                                           jnp.asarray(i + d - 1, I32))
            law = _gather_all(j, C, la_blocks, ws_j)
            fdw = _gather_all(j, C, fd_blocks, ws_p)
            cnt_ab = _ss_all(j, C, w, law, fdw, n)
            mb_j = state.mbit[sanitize(ws_j, cfg.e_cap)]
            votes, famous_i, und = j["fame_tally"](
                cnt_ab, valid_j, valid_p, valid_i, votes, famous_i,
                mb_j, jnp.asarray(d, I32),
            )
            und_any = bool(und)
            d += 1
        if stats is not None:
            # rounds-to-fame latency: the voting distance at which round
            # i's witnesses were all decided (BASELINE's north-star
            # metric); max_round+1 marks "ran out of voting rounds"
            stats.setdefault("fame_decision_distance", {})[i_abs] = (
                d - 1 if not und_any else None
            )
            stats["fame_vote_steps"] = stats.get("fame_vote_steps", 0) \
                + (d - 2)
        famous = j["fame_write"](famous, famous_i, jnp.asarray(i, I32))
    state = state._replace(famous=famous)
    return state._replace(lcr=j["fame_fin"](state, famous))


def run_wide_order(cfg: DagConfig, state: DagState, la_blocks, fd_blocks,
                   C: int, stats=None,
                   r_lo_abs: Optional[int] = None,
                   r_hi_abs: Optional[int] = None) -> DagState:
    """Blocked host-driven round-received + median timestamps (device
    twin: decide_order_impl, differentially tested).

    ``r_lo_abs``/``r_hi_abs`` restrict the round-received scan to the
    absolute rounds decided since the last call (windowed streaming):
    rounds decided earlier already tested every event then present, and
    later-arriving events can never be received there (a witness cannot
    see an event inserted after it — ops/stream.py).  Default: all
    window rows (the batch path).  The median pass streams only the
    slot range containing newly-received rows."""
    j = _jits(cfg, C)
    w = j["width"]
    n, e1 = cfg.n, cfg.e_cap + 1
    r_off = int(state.r_off)
    lo_r = 0 if r_lo_abs is None else max(0, r_lo_abs - r_off)
    hi_r = cfg.r_cap if r_hi_abs is None else min(
        cfg.r_cap, r_hi_abs - r_off + 1
    )
    seqw, fam, decided, has_w, fam_cnt, und = j["order_prep"](state)

    rr = state.rr
    stacked = _is_stacked(fd_blocks)
    offs = _block_offs(C, w) if stacked else None
    for i in range(lo_r, hi_r):
        if stacked:
            c = j["sees_stacked"](fd_blocks, seqw[i], fam[i], offs)
        else:
            c = jnp.zeros((e1,), I32)
            for blk in range(C):
                c = j["sees_partial_block"](
                    fd_blocks[blk], seqw[i], fam[i],
                    jnp.asarray(blk * w, I32), c,
                )
        rr = j["order_rr_update"](state, und, decided[i], has_w[i],
                                  fam_cnt[i], jnp.asarray(i, I32), c, rr)
    newly = und & (rr != -1)
    i_of = jnp.clip(rr - state.r_off, 0, cfg.r_cap - 1)

    # only the slot range holding newly-received rows needs the median
    n_lo, n_hi = j["newly_range"](newly)
    n_lo, n_hi = int(n_lo), int(n_hi)
    if n_hi <= n_lo:
        if stats is not None:   # accumulate-only: streaming reuses stats
            stats.setdefault("median_chunks", 0)
            stats.setdefault("median_chunk_rows", j["med_chunk"])
            stats.setdefault("median_rel32", True)
            stats.setdefault("median_bad_rows", 0)
        return state._replace(rr=rr)

    tmin, tmax, div1000 = j["ts_range"](state)
    span = int(np.asarray(tmax - tmin))
    scale = 1000 if (bool(np.asarray(div1000))
                     and span // 1000 < (1 << 31) - 1
                     and span >= (1 << 31) - 1) else 1
    rel32 = span // scale < (1 << 31) - 1
    scale_j = jnp.asarray(scale, jnp.int64)
    cts = state.cts
    chunk = min(j["med_chunk"], e1)
    bad_total = jnp.zeros((), I32)
    n_chunks = 0
    for k, e0 in enumerate(range(n_lo, n_hi, chunk)):
        e0 = min(e0, e1 - chunk)
        e0j = jnp.asarray(e0, I32)
        i_rows = j["slice_rows"](i_of, e0j, chunk)
        new_rows = j["slice_rows"](newly, e0j, chunk)
        if stacked:
            fd_rows = j["slice_stacked"](fd_blocks, e0j, chunk)
            tv_full, cnt_s, bad_rows = j["med_tv_stacked"](
                state, fd_rows, i_rows, seqw, fam, offs, tmin,
                scale_j, rel32,
            )
            bad_total = bad_total + (bad_rows & new_rows).sum(dtype=I32)
        else:
            tvs, cnts = [], []
            for blk in range(C):
                fd_rows = j["slice_rows"](fd_blocks[blk], e0j, chunk)
                tv_b, cnt_b, bad_b = j["med_tv_block"](
                    state, fd_rows, i_rows, seqw, fam,
                    jnp.asarray(blk * w, I32), tmin, scale_j, rel32,
                )
                tvs.append(tv_b)
                cnts.append(cnt_b)
                bad_total = bad_total + (bad_b & new_rows).sum(dtype=I32)
            tv_full = jnp.concatenate(tvs, axis=1)[:, :n]
            cnt_s = sum(cnts[1:], cnts[0])
        cts_rows = j["slice_rows"](cts, e0j, chunk)
        upd = j["med_reduce"](tv_full, cnt_s, new_rows, cts_rows, tmin,
                              scale_j, rel32)
        cts = j["write_rows"](cts, e0j, upd)
        n_chunks += 1
        if k % 8 == 7:
            _ = np.asarray(cts[:1])      # dispatch backpressure
    bad = int(bad_total)
    if bad:
        raise AssertionError(
            f"median read {bad} below-window first-descendants for "
            "newly-ordered rows — eviction policy violated "
            "(ops/stream.py margin contract)"
        )
    if stats is not None:
        stats["median_chunks"] = stats.get("median_chunks", 0) + n_chunks
        stats["median_chunk_rows"] = chunk
        stats["median_rel32"] = rel32
        stats["median_bad_rows"] = stats.get("median_bad_rows", 0)
    return state._replace(rr=rr, cts=cts)


def run_wide_pipeline(
    cfg: DagConfig,
    batch: EventBatch,
    state: Optional[DagState] = None,
    fd_mode: str = "fast",
    timings: Optional[dict] = None,
    n_blocks: Optional[int] = None,
    assemble: bool = True,
    stats: Optional[dict] = None,
) -> DagState:
    """Full batch pipeline at wide N: coords -> rounds -> fame -> order.

    ``timings``, if given, receives per-phase wall seconds (the hook the
    bench's MFU accounting uses).  ``assemble=False`` skips rebuilding
    the full [E+1, N] la/fd from their blocks (they would not fit next
    to the blocks at the 10k-deep configs); the returned state then has
    la/fd = None and only consensus-observable fields are meaningful.
    """
    import time

    if fd_mode != "fast":
        raise ValueError("wide pipeline supports the 'fast' batch mode")
    C = n_blocks or block_count(cfg)
    if stats is not None:
        stats["n_blocks"] = C
        stats["onehot_partials"] = _use_onehot_partial(cfg)
        stats["levels"] = int(batch.sched.shape[0])

    def tick(name, t0):
        if timings is not None:
            timings[name] = timings.get(name, 0.0) + time.perf_counter() - t0

    if state is None:
        state = init_state(cfg, include_coords=False)
    if int(state.r_off) != 0 or int(state.e_off) != 0:
        raise ValueError(
            "run_wide_pipeline is the one-shot batch wrapper; drive "
            "compacted/windowed states through ops.stream.WideStream"
        )
    # discard the fused-layout coordinate tensors: the wide path owns
    # its blocked twins (split is only needed when resuming mid-state,
    # which the batch pipeline never does — state is fresh)
    la_full, fd_full = state.la, state.fd
    if la_full is not None and int(state.n_events) > 0:
        la_blocks = _split_blocks(cfg, C, la_full, -1)
        fd_blocks = _split_blocks(cfg, C, fd_full, cfg.fd_inf)
    else:
        la_blocks, fd_blocks = _init_blocks(cfg, C)
    state = state._replace(la=None, fd=None)
    del la_full, fd_full
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    state, la_blocks, fd_blocks = run_wide_coords(
        cfg, state, batch, la_blocks, fd_blocks, C
    )
    _ = np.asarray(state.n_events)    # hard sync for honest phase timing
    jax.block_until_ready(la_blocks + fd_blocks)
    _ = np.asarray(la_blocks[0][:1, :1])
    tick("coords", t0)
    t0 = time.perf_counter()
    state = run_wide_rounds(cfg, state, la_blocks, fd_blocks, C, stats)
    _ = np.asarray(state.max_round)
    tick("rounds", t0)
    t0 = time.perf_counter()
    state = run_wide_fame(cfg, state, la_blocks, fd_blocks, C, stats)
    _ = np.asarray(state.lcr)
    tick("fame", t0)
    t0 = time.perf_counter()
    state = run_wide_order(cfg, state, la_blocks, fd_blocks, C, stats)
    _ = np.asarray(state.rr[:1])
    tick("order", t0)
    if assemble:
        state = state._replace(
            la=_assemble_blocks(cfg, la_blocks),
            fd=_assemble_blocks(cfg, fd_blocks),
        )
    return state
