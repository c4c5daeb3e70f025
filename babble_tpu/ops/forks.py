"""Fork-aware (byzantine-mode) consensus pipeline: dense branch kernels.

Semantics anchor: consensus/byzantine.py (the definition-first oracle);
differential tests assert bit-equality.  The reference has no counterpart —
it rejects forks at insert (hashgraph.go:366-396) — so this module is the
framework's answer to the BASELINE "1/3 byzantine forks" config and
SURVEY §7 hard-part 4 ("fork handling breaks the coordinate trick").

TPU formulation
---------------
The honest engine's coordinate trick indexes la/fd by *creator*; forks
break it because a creator may have several events per index.  Here the
column axis is (creator, branch-slot): each creator owns K consecutive
columns, branch b of creator i lives at column i*K + k.  That grouping is
the load-bearing choice: every "per creator" reduction (strongly-see
counts creators, not branches) becomes a reshape to [..., N, K] followed
by any/max — pure VPU work that XLA fuses, no segment ops, no one-hot
matmuls.

A branch's *chain* is the full root→tip path, so chains share prefixes.
``cp[B, B]`` (common-prefix lengths, host-built) decides membership:
event (b, q) is on chain(b') iff q < cp[b, b'].  Everything else follows
the paper's definitions:

- ``la[x, b]``: highest chain-(b) index among x's ancestors (level scan;
  an event contributes its index to every chain containing it).
- fork detection is a *pure function of la*: creator i's fork pair
  (k1, k2) is visible to x iff la reaches past the pair's common prefix
  on both branches.  No extra propagation pass needed.
- ``see(x, y) = la[x, br(y)] >= seq(y) and not det[x, creator(y)]``.
- ``first_det[b, c]``: first index on chain(b) whose event detects a fork
  by c.  Both ancestry and detection are monotone along a chain, so "the
  events on branch b that see y" form the interval
  [fd[y, b], first_det[b, creator(y)]) — ``helper[y, b]`` is its left end
  (INF when empty), and strongly-see is the creator-count of
  ``la[x, b] >= helper[y, b]`` — the same compare-count shape as the
  honest kernels, one branch axis wider.

Batch mode: built for whole-DAG ingestion from a fresh state (the
byzantine bench + differential path); the engine's live byzantine mode
re-runs it per sync window.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dag import clamp_eff_ts
from ..core.event import Event
from .state import (
    I32, I64, INT32_MAX, count_below_sorted, sanitize, set_sentinel,
)
from ..membership.quorum import supermajority

F32 = jnp.float32

FAME_UNDEFINED = 0
FAME_TRUE = 1
FAME_FALSE = 2


class ForkConfig(NamedTuple):
    n: int          # creators
    k: int          # branch slots per creator (1 = honest)
    e_cap: int
    s_cap: int      # chain-index capacity (root->tip length)
    r_cap: int

    @property
    def b(self) -> int:
        return self.n * self.k

    @property
    def super_majority(self) -> int:
        return supermajority(self.n)


class ForkBatch(NamedTuple):
    """Whole-DAG host-built arrays (slots = insertion order).

    ``rseed``/``wseed`` support the rolling live window
    (fork_engine.maybe_compact): both round and witness status are
    functions of an event's fixed ancestry, so values computed in an
    earlier run are final and seed the next run — the closure then only
    assigns NEW events, and events whose parents were evicted keep
    exact rounds.  Seeds are window-LOCAL rounds (absolute - r_off,
    with r_off = the minimum retained round so every seed is >= 0);
    -1 = not yet computed."""

    sp: jnp.ndarray       # i32[E+1] self-parent slot, -1 (sentinel row incl.)
    op: jnp.ndarray       # i32[E+1]
    ebr: jnp.ndarray      # i32[E+1] branch column of event; B = dump
    eseq: jnp.ndarray     # i32[E+1] chain index of event; -1 sentinel
    ecr: jnp.ndarray      # i32[E+1] creator; N = dump
    ts: jnp.ndarray       # i64[E+1]
    mbit: jnp.ndarray     # bool[E+1]
    sched: jnp.ndarray    # i32[T, Bt] slots by level, -1 pad
    cp: jnp.ndarray       # i32[B, B] common-prefix lengths (diag = INF)
    ce: jnp.ndarray       # i32[B, S+1] chain view (slots, -1 pad)
    cnt: jnp.ndarray      # i32[B] chain lengths (0 for unused branch slots)
    owner: jnp.ndarray    # bool[B, S+1] position is owned (assigned) by b
    n_events: jnp.ndarray # i32
    rseed: jnp.ndarray    # i32[E+1] seeded window-local round, -1 unknown
    wseed: jnp.ndarray    # i8[E+1]  seeded witness trilean (-1/0/1)
    s_off: jnp.ndarray    # i32[B] absolute chain index of window position 0


class ForkOut(NamedTuple):
    """Consensus outputs (per event / per witness-branch)."""

    la: jnp.ndarray       # i32[E+1, B]
    det: jnp.ndarray      # bool[E+1, N]
    fd: jnp.ndarray       # i32[E+1, B]
    round: jnp.ndarray    # i32[E+1]
    witness: jnp.ndarray  # bool[E+1]
    wslot: jnp.ndarray    # i32[R+1, B]
    famous: jnp.ndarray   # i8[R+1, B]
    rr: jnp.ndarray       # i32[E+1]
    cts: jnp.ndarray      # i64[E+1]
    max_round: jnp.ndarray
    lcr: jnp.ndarray
    closure_steps: jnp.ndarray  # i32: descent-closure iterations, all rounds
    vote_steps: jnp.ndarray     # i32: diagonal vote steps fame ran
    band_fallbacks: jnp.ndarray  # i32: rounds the closure ran full-width


# ----------------------------------------------------------------------
# host: branch assignment + chain views


class ForkBudgetError(ValueError):
    """Creator exceeded its K-1 fork budget (equivocation spam guard)."""


class ParentUnknownError(ValueError):
    """Event references a parent hash outside the window — a missing-
    ancestry case that a deeper resync can heal, as opposed to a
    malformed or forged event (ADVICE r4 low: Core.sync classifies
    insert failures by type, not message substring)."""


class _BranchLayout:
    """ForkDag's branch rules, shared by the live index (``ForkDag``,
    event by event) and a recorded DAG's plain arrays (``ForkArrays``):
    which branch column an event joins, the per-slot bookkeeping, each
    column's root->tip chain, the common-prefix matrix and the chain
    views.  Subclasses provide ``k``, ``b`` and ``_index(slot)`` (an
    event's chain index)."""

    def _init_layout(self, n: int) -> None:
        """An empty layout of ``n`` creators' ``k`` columns each."""
        b = n * self.k
        # per slot: level, parent slots (-1 a root or evicted), column
        self.levels: List[int] = []
        self.sp_slot: List[int] = []
        self.op_slot: List[int] = []
        self.ebr: List[int] = []
        # rolling-window seeds (ForkBatch docstring): ABSOLUTE round and
        # witness trilean per slot, -1 until the pipeline computes them;
        # r_off = absolute round of window row 0
        self.rseed: List[int] = []
        self.wseed: List[int] = []
        self.r_off = 0
        # effective (clamp-enforced) timestamp per slot — same adversarial-ts
        # defense as HostDag.eff_ts (core/dag.py TS_CLAMP_WINDOW_NS), derived
        # at insert from the parents' effective values.  The median kernels
        # consume these, never the signed claims; a fork's branches clamp
        # against their own ancestry, so equivocating AND lying about time
        # buys a byzantine creator nothing extra.
        self.eff_ts: List[int] = []
        # per branch column: creator, parent branch col (-1), divergence
        # index, the slots of OWNED events (the segment past the
        # divergence), the tip slot, and the absolute chain extent (max
        # index + 1, which survives eviction, unlike window lengths)
        self.br_creator = [c for c in range(n) for _ in range(self.k)]
        self.br_parent = [-1] * b
        self.br_div = [0] * b
        self.br_events: List[List[int]] = [[] for _ in range(b)]
        self.br_used = [False] * b
        self._chain_tip: Dict[int, int] = {}
        self.br_extent = [0] * b
        # per-CREATOR slots in insertion order (the gossip Known/diff
        # view) and evicted counts (the gossip vector clock stays absolute)
        self.cr_events: List[List[int]] = [[] for _ in range(n)]
        self.cr_evicted = [0] * n

    def _claim_column(self, cid: int, sps: int, index: int) -> int:
        """The branch column of a new event of creator ``cid`` with
        self-parent slot ``sps`` (-1 for a root): its parent's column if
        the parent is that column's tip, else a fresh column of the
        creator's (a fork), within the K-1 fork budget."""
        if sps < 0:
            col = cid * self.k
            if self.br_used[col]:
                raise ValueError("duplicate root (index-0 fork unsupported)")
            self.br_used[col] = True
            return col
        pcol = self.ebr[sps]
        if self._chain_tip.get(pcol) == sps:
            return pcol                         # extends the branch tip
        for kk in range(self.k):
            col = cid * self.k + kk
            if not self.br_used[col]:
                self.br_used[col] = True
                self.br_parent[col] = pcol
                self.br_div[col] = index
                return col
        raise ForkBudgetError(f"creator {cid} exceeded {self.k - 1} forks")

    def _place(self, cid: int, sps: int, ops: int, index: int,
               claimed_ts: int) -> int:
        """Record a new event (its parents known, or -1 past the window)
        in the next slot; returns the slot."""
        col = self._claim_column(cid, sps, index)
        slot = len(self.ebr)
        self.cr_events[cid].append(slot)
        self.sp_slot.append(sps)
        self.op_slot.append(ops)
        self.ebr.append(col)
        self.br_events[col].append(slot)
        self._chain_tip[col] = slot
        self.br_extent[col] = max(self.br_extent[col], index + 1)
        self.rseed.append(-1)
        self.wseed.append(-1)
        # per-creator eff-ts clamp (engine-parity: timestamp-clamp) —
        # evicted parents contribute nothing, same as HostDag pseudo-roots
        parent_ref = None
        if sps >= 0:
            parent_ref = self.eff_ts[sps]
        if ops >= 0:
            op_eff = self.eff_ts[ops]
            parent_ref = op_eff if parent_ref is None \
                else max(parent_ref, op_eff)
        self.eff_ts.append(clamp_eff_ts(claimed_ts, parent_ref))
        lvl = 0
        if sps >= 0 or ops >= 0:
            lvl = 1 + max(
                self.levels[sps] if sps >= 0 else -1,
                self.levels[ops] if ops >= 0 else -1,
            )
        self.levels.append(lvl)
        return slot

    def _chain_slots(self, col: int) -> List[int]:
        """Full root->tip slot list of branch col (inherited prefix +
        owned segment)."""
        segs = []
        c, upto = col, None
        while c >= 0:
            seg = self.br_events[c]
            if upto is not None:
                seg = [s for s in seg if self._index(s) < upto]
            segs.append(seg)
            upto = self.br_div[c]
            c = self.br_parent[c]
        out: List[int] = []
        for seg in reversed(segs):
            out.extend(seg)
        return out

    def common_prefix(self) -> np.ndarray:
        """cp[b1, b2]: shared chain-prefix length (diag INF-ish)."""
        b = self.b
        cp = np.zeros((b, b), np.int32)

        def path(col):
            # list of (col, div) from root segment to col
            p = []
            c = col
            while c >= 0:
                p.append(c)
                c = self.br_parent[c]
            return list(reversed(p))

        paths = [path(c) if self.br_used[c] else [] for c in range(b)]
        # ABSOLUTE chain extents: window lengths would understate
        # divergence fallbacks after prefix eviction
        lens = list(self.br_extent)
        for b1 in range(b):
            if not self.br_used[b1]:
                continue
            for b2 in range(b):
                if not self.br_used[b2]:
                    continue
                if self.br_creator[b1] != self.br_creator[b2]:
                    cp[b1, b2] = 0
                    continue
                if b1 == b2:
                    cp[b1, b2] = INT32_MAX
                    continue
                p1, p2 = paths[b1], paths[b2]
                common = 0
                for a, bb in zip(p1, p2):
                    if a != bb:
                        break
                    common += 1
                # divergence = div of the first differing segment (the
                # shared prefix ends where either path leaves the last
                # common segment)
                d1 = (self.br_div[p1[common]] if common < len(p1)
                      else lens[b1])
                d2 = (self.br_div[p2[common]] if common < len(p2)
                      else lens[b2])
                cp[b1, b2] = min(d1, d2)
        return cp

    def _fork_batch(self, cfg: ForkConfig, eseq: np.ndarray,
                    ecr: np.ndarray, mbit: np.ndarray,
                    sched: np.ndarray) -> ForkBatch:
        """The ForkBatch of the slots placed so far: ``eseq``, ``ecr``
        and ``mbit`` per slot, ``sched`` the level schedule."""
        e1 = cfg.e_cap + 1
        ne = len(self.ebr)
        if ne > cfg.e_cap:
            raise ValueError(f"e_cap {cfg.e_cap} < {ne} events")
        B, s1 = cfg.b, cfg.s_cap + 1

        def pad1(a, fill, dtype):
            out = np.full(e1, fill, dtype)
            out[:ne] = a
            return out

        ce = np.full((B, s1), -1, np.int32)
        owner = np.zeros((B, s1), bool)
        cnt = np.zeros(B, np.int32)
        s_off = np.zeros(B, np.int32)
        ebr = np.asarray(self.ebr, np.int32)
        for col in range(B):
            if not self.br_used[col]:
                continue
            chain = self._chain_slots(col)
            if len(chain) > cfg.s_cap:
                raise ValueError(f"s_cap {cfg.s_cap} < chain {len(chain)}")
            ce[col, : len(chain)] = chain
            cnt[col] = len(chain)
            # window positions map to absolute chain indexes by a per-
            # branch offset (contiguous: prefix eviction drops a chain
            # prefix, and chain indexes step by one)
            s_off[col] = self._index(chain[0]) if chain else 0
            owner[col, : len(chain)] = ebr[chain] == col

        rseed = np.asarray(self.rseed, np.int64)
        seeded = rseed >= 0
        return ForkBatch(
            sp=jnp.asarray(pad1(self.sp_slot, -1, np.int32)),
            op=jnp.asarray(pad1(self.op_slot, -1, np.int32)),
            ebr=jnp.asarray(pad1(ebr, B, np.int32)),
            eseq=jnp.asarray(pad1(eseq, -1, np.int32)),
            ecr=jnp.asarray(pad1(ecr, cfg.n, np.int32)),
            # effective (clamped) timestamps, never the signed claims —
            # the adversarial-ts defense's single seam, like dag.eff_ts
            ts=jnp.asarray(pad1(self.eff_ts, 0, np.int64)),
            mbit=jnp.asarray(pad1(mbit, False, bool)),
            sched=jnp.asarray(sched), cp=jnp.asarray(self.common_prefix()),
            ce=jnp.asarray(ce), cnt=jnp.asarray(cnt),
            owner=jnp.asarray(owner), n_events=jnp.asarray(ne, jnp.int32),
            rseed=jnp.asarray(pad1(
                np.where(seeded, rseed - self.r_off, -1), -1, np.int32)),
            wseed=jnp.asarray(pad1(
                np.where(seeded, self.wseed, -1), -1, np.int8)),
            s_off=jnp.asarray(s_off),
        )


@dataclass
class ForkDag(_BranchLayout):
    """Host index for byzantine mode: assigns branch columns, builds the
    chain views + common-prefix matrix the kernels need."""

    participants: Dict[str, int]
    k: int = 2

    events: List[Event] = field(default_factory=list)
    slot_of: Dict[str, int] = field(default_factory=dict)
    evicted: int = 0                  # slots dropped by evict_prefix, total

    def __post_init__(self):
        self._init_layout(len(self.participants))

    @property
    def n(self) -> int:
        return len(self.participants)

    @property
    def b(self) -> int:
        return self.n * self.k

    def _index(self, slot: int) -> int:
        return self.events[slot].index

    def insert(self, event: Event) -> int:
        x = event.hex()
        if x in self.slot_of:
            raise ValueError("duplicate event")
        cid = self.participants[event.creator]
        sp, op = event.self_parent, event.other_parent
        if sp == "" and op == "":
            if event.index != 0:
                raise ValueError("root must have index 0")
            sps = ops = -1
        else:
            sps = self.slot_of.get(sp, -1)
            ops = self.slot_of.get(op, -1)
            if sps < 0 or ops < 0:
                raise ParentUnknownError("parent not known")
            spe = self.events[sps]
            if spe.creator != event.creator:
                raise ValueError("self-parent has different creator")
            if event.index != spe.index + 1:
                raise ValueError("bad index")
        slot = self._place(cid, sps, ops, event.index, event.body.timestamp)
        self.events.append(event)
        self.slot_of[x] = slot
        event.topological_index = self.evicted + slot
        return slot

    # ------------------------------------------------------------------

    def evict_prefix(self, k: int, new_r_off: int) -> None:
        """Drop the first k slots (a committed prefix the engine proved
        safe — fork_engine.maybe_compact) and rebase slot references.
        Slot order is insertion order and chain positions ascend with
        slot, so a slot prefix is a chain prefix on every branch; chain
        INDEX values (eseq, cp, la/fd units) are absolute and survive
        unchanged.  Evicted parents become -1: the pipeline treats such
        events as pseudo-roots whose round/witness come from rseed/wseed
        instead of the root rule."""
        if k <= 0:
            self.r_off = new_r_off
            return
        for s in range(k):
            del self.slot_of[self.events[s].hex()]
        self.events = self.events[k:]
        self.levels = self.levels[k:]
        self.rseed = self.rseed[k:]
        self.wseed = self.wseed[k:]
        self.eff_ts = self.eff_ts[k:]

        def remap(v: int) -> int:
            return v - k if v >= k else -1

        self.sp_slot = [remap(v) for v in self.sp_slot[k:]]
        self.op_slot = [remap(v) for v in self.op_slot[k:]]
        self.ebr = self.ebr[k:]
        for h in list(self.slot_of):
            self.slot_of[h] -= k
        self.br_events = [
            [s - k for s in lst if s >= k] for lst in self.br_events
        ]
        for cid, lst in enumerate(self.cr_events):
            kept = [s - k for s in lst if s >= k]
            self.cr_evicted[cid] += len(lst) - len(kept)
            self.cr_events[cid] = kept
        self._chain_tip = {
            col: s - k for col, s in self._chain_tip.items() if s >= k
        }
        self.evicted += k
        self.r_off = new_r_off

    def build_batch(self, cfg: ForkConfig) -> ForkBatch:
        lev = np.asarray(self.levels, np.int64)
        order = np.argsort(lev, kind="stable")
        ulev, starts = np.unique(lev[order], return_index=True)
        bounds = list(starts) + [len(self.events)]
        # bucket the schedule dims to powers of two (state.bucket):
        # exact (levels, widest-level) shapes change almost every
        # consensus tick, and each distinct shape is a full pipeline
        # re-trace — bucketing collapses the shape universe so a steady
        # fleet reuses a handful of programs (and the AOT prewarm can
        # replay them at boot).  Padding rows/lanes hold -1 slots the
        # level scan already ignores, so outputs are bit-identical.
        from .state import bucket as _bkt

        t = _bkt(max(len(ulev), 1), 1)
        wid = _bkt(
            max(int(np.max(np.diff(bounds))), 1) if len(ulev) else 1, 1
        )
        sched = np.full((t, wid), -1, np.int32)
        for row in range(len(ulev)):
            grp = order[bounds[row] : bounds[row + 1]]
            sched[row, : len(grp)] = grp
        evs = self.events
        return self._fork_batch(
            cfg,
            eseq=np.asarray([ev.index for ev in evs], np.int32),
            ecr=np.asarray([self.participants[ev.creator] for ev in evs],
                           np.int32),
            mbit=np.asarray([ev.middle_bit() for ev in evs], bool),
            sched=sched,
        )


class ForkArrays(_BranchLayout):
    """A recorded DAG's plain arrays (slot order topological; ``sp`` /
    ``op`` parent slots, -1 for roots) under ForkDag's branch rules —
    the batch path's twin of inserting every event into a ForkDag."""

    def __init__(self, n: int, k: int, sp, op, creator, seq, ts):
        self.k = k
        self.b = n * k
        self._seq = np.asarray(seq, np.int32)
        sp, op = np.asarray(sp), np.asarray(op)
        creator = self._creator = np.asarray(creator, np.int32)
        slots = np.arange(len(sp))
        if np.any((creator < 0) | (creator >= n)):
            raise ValueError(f"a creator outside 0..{n - 1}")
        root = sp < 0
        if np.any(root != (op < 0)) or np.any(self._seq[root] != 0):
            raise ValueError("a root must have index 0 and no parents")
        if np.any((sp >= slots) | (op >= slots)):
            raise ValueError("slots are not in topological order")
        spx = np.where(root, 0, sp)
        if np.any(~root & ((creator[spx] != creator)
                           | (self._seq[spx] + 1 != self._seq))):
            raise ValueError("a self-parent of another creator or index")
        self._init_layout(n)
        for c, p, q, i, t in zip(creator.tolist(), sp.tolist(), op.tolist(),
                                 self._seq.tolist(), np.asarray(ts).tolist()):
            self._place(c, p, q, i, t)

    def _index(self, slot: int) -> int:
        return int(self._seq[slot])

    def build_batch(self, cfg: ForkConfig, mbit,
                    sched: np.ndarray) -> ForkBatch:
        """The ForkBatch of every event, ``mbit`` its coin bits and
        ``sched`` the level schedule."""
        return self._fork_batch(cfg, self._seq, self._creator,
                                np.asarray(mbit, bool), sched)


# ----------------------------------------------------------------------
# device kernels


def _la_scan(cfg: ForkConfig, b: ForkBatch) -> jnp.ndarray:
    """la[x, br] = highest chain-(br) index among x's ancestors."""
    e1, B = cfg.e_cap + 1, cfg.b
    la0 = jnp.full((e1, B), -1, I32)

    # own contribution row per event: index on every chain containing it
    def step(la, idx):
        idx_s = sanitize(idx, cfg.e_cap)
        spx = sanitize(b.sp[idx_s], cfg.e_cap)
        opx = sanitize(b.op[idx_s], cfg.e_cap)
        rows = jnp.maximum(la[spx], la[opx])                  # [Bt, B]
        q = b.eseq[idx_s]                                     # [Bt]
        cp_rows = b.cp[jnp.clip(b.ebr[idx_s], 0, B - 1)]      # [Bt, B]
        own = jnp.where(
            (cp_rows > q[:, None]) & (q[:, None] >= 0), q[:, None], -1
        )
        rows = jnp.maximum(rows, own)
        rows = jnp.where((idx >= 0)[:, None], rows, -1)
        return la.at[idx_s].set(rows), None

    la, _ = jax.lax.scan(step, la0, b.sched)
    # sentinel row stays -1 (pad lanes all dumped -1 rows into it).
    # set_sentinel, not .at[e_cap].set: the pipeline runs sharded
    # (make_sharded_fork_step) and a static-index row write clamps
    # per shard under SPMD (ops/state.py set_sentinel docstring)
    e_row = (jnp.arange(cfg.e_cap + 1) == cfg.e_cap)[:, None]
    return set_sentinel(la, e_row, -1)


def _detect(cfg: ForkConfig, b: ForkBatch, la: jnp.ndarray) -> jnp.ndarray:
    """det[x, i]: x's ancestry contains a fork pair by creator i — a pure
    function of la: some pair of i's branches is visible past their common
    prefix."""
    n, k, B = cfg.n, cfg.k, cfg.b
    lg = la.reshape(la.shape[0], n, k)                        # [E+1, N, K]
    cpg = b.cp.reshape(n, k, n, k)
    # per-creator K x K common-prefix block
    cpk = cpg[jnp.arange(n), :, jnp.arange(n), :]             # [N, K, K]
    vis = lg[:, :, :, None] >= cpk[None, :, :, :]             # [E+1, N, K, K]
    pair = vis & jnp.swapaxes(vis, -1, -2)
    off = ~jnp.eye(k, dtype=bool)
    return (pair & off[None, None]).any(axis=(-1, -2))        # [E+1, N]


def _first_det(cfg: ForkConfig, b: ForkBatch, det: jnp.ndarray) -> jnp.ndarray:
    """first_det[br, c]: first ABSOLUTE chain index on branch br whose
    event detects a fork by c (INT32_MAX if none).  Detection is
    monotone along a chain, so it's a count of the False prefix plus the
    branch's window offset.  Window note: a detection by an EVICTED
    prefix event would be missed here, but eviction only drops ordered
    events below the round window, whose detection cut-offs only affect
    already-decided rounds."""
    dchain = det[sanitize(b.ce, cfg.e_cap)]                   # [B, S+1, N]
    live = (jnp.arange(cfg.s_cap + 1)[None, :] < b.cnt[:, None])
    pre = (~dchain) & live[:, :, None]
    first = pre.sum(axis=1, dtype=I32) + b.s_off[:, None]     # [B, N]
    hit = (dchain & live[:, :, None]).any(axis=1)
    return jnp.where(hit, first, INT32_MAX)


def _fd_reverse(cfg: ForkConfig, b: ForkBatch) -> jnp.ndarray:
    """First-descendant fill by reverse level scan — the fork-aware twin
    of ingest._fd_reverse_scan.  Walking levels deepest-first, an event's
    fd row is final before its parents absorb it by scatter-min; the own
    contribution covers every chain containing the event (cp mask), so
    shared prefixes inherit descendants from all branches.  O(E·B)
    against the chain-view compare-count's O(E²) (~9 s at the 1024x100k
    byzantine bench)."""
    B = cfg.b
    q = b.eseq
    cp_rows = b.cp[jnp.clip(b.ebr, 0, B - 1)]                 # [E+1, B]
    fd0 = jnp.where(
        (cp_rows > q[:, None]) & (q[:, None] >= 0), q[:, None], INT32_MAX
    ).astype(I32)

    def step(fd, idx):
        idx_s = sanitize(idx, cfg.e_cap)
        rows = fd[idx_s]
        spx = sanitize(b.sp[idx_s], cfg.e_cap)
        opx = sanitize(b.op[idx_s], cfg.e_cap)
        fd = fd.at[spx].min(rows)
        fd = fd.at[opx].min(rows)
        return fd, None

    fd, _ = jax.lax.scan(step, fd0, b.sched[::-1])
    # SPMD-safe sentinel restore (see _la_scan)
    e_row = (jnp.arange(cfg.e_cap + 1) == cfg.e_cap)[:, None]
    return set_sentinel(fd, e_row, INT32_MAX)


#: elements of one chain chunk's view slab [Cb, S+1, B] in ``_fd_chains``
#: (512 MB of i32): the whole view at once needs 17.2 GB at 1,024
#: validators x 2 branch slots, 131,072 events, a schedule deep enough
#: for this form (described v5e); the cells' shape is one chunk
_CHAIN_CHUNK_ELEMS = 2 ** 27


def _fd_chains(cfg: ForkConfig, b: ForkBatch, la: jnp.ndarray) -> jnp.ndarray:
    """fd[y, br] = first chain-(br) index of a descendant of y: the count,
    on chain br, of positions whose event is not yet a descendant of y,
    over the monotone chain view (state.count_below_sorted, the _fd_full
    pattern with a branch axis).  The chain axis runs in chunks of
    ``_CHAIN_CHUNK_ELEMS``; each fills its own fd columns."""
    B = cfg.b
    s_idx = jnp.arange(cfg.s_cap + 1)
    # thresholds are ABSOLUTE target-chain indexes (window position t on
    # chain `by` is index t + s_off[by])
    thr = s_idx[None, :] + b.s_off[:, None]                   # [B, S+1]
    # owned-target grid (rows disjoint across chains)
    tgt = sanitize(jnp.where(b.owner, b.ce, -1), cfg.e_cap)   # [B, S+1]
    cb = max(1, min(B, _CHAIN_CHUNK_ELEMS // ((cfg.s_cap + 1) * B)))
    blocks = []
    for c0 in range(0, B, cb):
        ce, cnt = b.ce[c0:c0 + cb], b.cnt[c0:c0 + cb]
        live = s_idx[None, :] < cnt[:, None]                  # [Cb, S+1]
        V = jnp.where(live[:, :, None], la[sanitize(ce, cfg.e_cap)],
                      INT32_MAX)                              # [Cb, S+1, B]
        out = count_below_sorted(V, thr)                      # [Cb, B, T]
        found = out < cnt[:, None, None]
        # counts are window positions on the source chain -> absolute
        out = jnp.where(found, out + b.s_off[c0:c0 + cb, None, None],
                        INT32_MAX)
        # fd[ce[by, t], br] = out[br, by, t]
        blk = jnp.full((cfg.e_cap + 1, out.shape[0]), INT32_MAX, I32)
        blocks.append(blk.at[tgt].set(out.transpose(1, 2, 0)))
    fd = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    e_row = (jnp.arange(cfg.e_cap + 1) == cfg.e_cap)[:, None]
    return set_sentinel(fd, e_row, INT32_MAX)


def _helper(cfg: ForkConfig, b: ForkBatch, fd: jnp.ndarray,
            first_det: jnp.ndarray) -> jnp.ndarray:
    """helper[y, br]: first chain-(br) index whose event *sees* y — the
    left end of the interval [fd, first_det[br, creator(y)]), INF when the
    first descendant already detects creator(y)'s fork."""
    fdet_y = first_det.T[jnp.clip(b.ecr, 0, cfg.n - 1)]       # [E+1, B]
    return jnp.where(fd < fdet_y, fd, INT32_MAX)


def _ss_counts(cfg: ForkConfig, la_x: jnp.ndarray, det_x: jnp.ndarray,
               helper_w: jnp.ndarray) -> jnp.ndarray:
    """Creator-count of strongly-see middlemen.

    la_x: [..., B] viewer coordinates; det_x: [..., N]; helper_w: [..., B]
    target helper rows (broadcast-compatible).  Returns i32[...] counts.

    The per-creator any() over the K branch slots is expressed as a
    static OR of K strided column slices (branch b of creator c lives at
    column c*K + b, so slice [k::K] is creator-major) — a reshape+any
    here blocks XLA from fusing the [..., B] compare into the reduction,
    materializing it (observed: the 536 MB x 3,125-step rounds scan that
    made byzantine mode 27x slower than honest, and a 68 GB pred at
    fame's [R, A, W, N] shape).  The OR keeps the whole chain
    compare->or->mask->reduce elementwise, which fuses."""
    ok = la_x[..., 0::cfg.k] >= helper_w[..., 0::cfg.k]       # [..., N]
    for kk in range(1, cfg.k):
        ok = ok | (la_x[..., kk::cfg.k] >= helper_w[..., kk::cfg.k])
    return (ok & ~det_x).sum(-1, dtype=I32)


#: Chain positions above each branch's frontier that one round of
#: ``_rounds_closure`` examines.  A round assigns the positions
#: ``[pos, t)`` of every branch; on the 4 x 65,536 benchmark DAGs one
#: creator mints 4.2 events a round on average and at most 34 (the fork
#: reference over 1,000 seeds, PERF.md section 3), so 64 leaves room.  A
#: round whose band runs out falls back to the full pass over the event
#: axis, exact at any width.
ROUND_BAND = 64


def _rounds_closure(cfg: ForkConfig, b: ForkBatch, la: jnp.ndarray,
                    det: jnp.ndarray, helper: jnp.ndarray,
                    band: int = ROUND_BAND):
    """Round assignment as a per-round closure iteration — the fork-aware
    analogue of the honest frontier march (ingest.py _rounds_frontier),
    replacing the level scan whose per-step witness gathers were ~90% of
    byzantine wall time (VERDICT r2 weak #3: 3,315 sequential steps,
    each gathering a [32, B, B] helper tensor).

    Per round r (at most max_round+1 iterations):

    - candidate witnesses = each branch's first not-yet-assigned event
      (the chain frontier ``pos``).  Some candidates' true rounds exceed
      r ("jumps" via the other parent); they are harmless in the
      supermajority count by the same ancestry-composition argument as
      the honest march: strongly-seeing a jumped candidate implies
      descending from it, and descent alone already lifts the seer past
      round r (rounds are monotone along parent edges).
    - S = unassigned events that strongly see >= 2n/3+1 candidate
      CREATORS (the fork-aware count: branch-OR, detection-masked), or
      whose parent's (seeded) round exceeds r.
    - round > r iff in the descent closure of S: D = S | D[sp] | D[op],
      iterated to fixpoint (rounds inherit through parents even when
      later fork detection would discount the middlemen — which is why
      the honest march's per-chain bisection does NOT port: the
      detection-masked count is not monotone along a chain).
    - everything unassigned outside D has round exactly r.

    Assigned rounds form a prefix of every chain and "round > r" (D plus
    the seeded events above r) a suffix, so one threshold per branch,
    ``t[b]``, describes D, and round r is exactly the positions
    ``[pos[b], t[b])``.  A round therefore works on a band of ``band``
    positions above each frontier, not on the event axis: S is counted
    for the band's events only, and the closure is a fixpoint over t.
    ``t[b]`` is the first band position whose event strongly sees a
    supermajority or is seeded above r, or whose other parent lies at or
    above its own column's threshold (which covers a parent seeded above
    r).  A parent's status is read from its owner column's t, so a
    common-prefix position, one event in several views, has one status;
    a self-parent is the previous position of the same view, so its
    status is already the view's own threshold.  Started from S and
    moving only down, t never marks an event that D lacks, and it
    reaches D unless a branch still has positions past its band and met
    no round > r inside it.  That round runs the full pass over the
    event axis instead, exact at any width, and counts in
    ``band_fallbacks``.  ``closure_steps`` sums the closure iterations
    of the pass that decided each round.

    Witness tables come from the frontier: branch b's round-r witness is
    its frontier event iff that event was assigned round r and b owns
    the position (shared fork prefixes belong to one branch column
    only).  Bit-parity with the byzantine oracle is pinned by
    tests/test_forks.py."""
    n, k, B, sm, r_cap = cfg.n, cfg.k, cfg.b, cfg.super_majority, cfg.r_cap
    e1 = cfg.e_cap + 1
    s_cap = cfg.s_cap
    W = min(band, s_cap + 1)
    rows = jnp.arange(B, dtype=I32)

    valid_e = (jnp.arange(e1) < b.n_events) & (b.eseq >= 0)
    spx = sanitize(b.sp, cfg.e_cap)
    opx = sanitize(b.op, cfg.e_cap)

    # seeds (rolling window): rounds/witness status are ancestry-fixed,
    # so values from earlier runs pre-assign the retained prefix and the
    # loop only decides events inserted since (ForkBatch docstring)
    seeded = valid_e & (b.rseed >= 0)
    rnd0 = jnp.where(seeded, b.rseed, -1)
    cex = sanitize(b.ce, cfg.e_cap)                          # [B, S+1]
    live_chain = (jnp.arange(s_cap + 1)[None, :] < b.cnt[:, None])

    # pre-populate witness rows from seeds: one owned witness per
    # (branch, seeded round)
    w_chain = (b.wseed[cex] == 1) & b.owner & live_chain \
        & (b.rseed[cex] >= 0)
    w_round = jnp.where(w_chain, b.rseed[cex], r_cap)        # dump row
    wslot0 = jnp.full((r_cap + 1, B), -1, I32)
    wslot0 = wslot0.at[
        jnp.clip(w_round, 0, r_cap), rows[:, None].repeat(s_cap + 1, 1)
    ].max(jnp.where(w_chain, b.ce, -1))

    def ss_ok(la_x, det_x, hw, valid_w):
        # x strongly sees >= sm candidate creators (branch-OR)
        ss_cnt = _ss_counts(cfg, la_x[..., None, :], det_x[..., None, :],
                            hw)                               # [..., B]
        ss = (ss_cnt >= sm) & valid_w
        ss_c = ss[..., 0::k]
        for kk in range(1, k):
            ss_c = ss_c | ss[..., kk::k]
        return ss_c.sum(-1) >= sm

    def witness_row(wslot, r, ws, is_w):
        # witness table row r: the frontier event, when it was assigned
        # round r and the branch owns the position (keep seeded entries
        # of other branches in the row)
        row = jnp.minimum(r, r_cap)
        return wslot.at[row].set(jnp.where(is_w, ws, wslot[row]))

    def full_round(r, rnd, wslot, steps):
        # the whole event axis: exact whatever the band saw
        rnd_chain = jnp.where(live_chain, rnd[cex], -1)
        pos = ((rnd_chain >= 0) & (rnd_chain < r)).sum(-1, dtype=I32)
        valid_w = pos < b.cnt
        ws = b.ce[rows, jnp.clip(pos, 0, s_cap)]
        wsx = sanitize(jnp.where(valid_w, ws, -1), cfg.e_cap)
        hw = jnp.where(valid_w[:, None], helper[wsx], INT32_MAX)  # [B, B]
        unassigned = valid_e & (rnd < 0)
        # parent rounds above r also lift (rounds are monotone through
        # parent edges) — this is what lets seeded boundaries skip the
        # rounds the window no longer has full ancestry for
        pr_gt = jnp.maximum(rnd[spx], rnd[opx]) > r
        S = unassigned & (ss_ok(la, det, hw, valid_w) | pr_gt)

        # descent closure of S within the unassigned set
        def cl_body(c):
            D, _, it = c
            D2 = S | (unassigned & (D[spx] | D[opx]))
            D2 = D2 & valid_e
            return D2, (D2 != D).any(), it + 1

        D, _, it = jax.lax.while_loop(
            lambda c: c[1], cl_body, (S, jnp.asarray(True), steps)
        )
        newly = unassigned & ~D
        rnd = jnp.where(newly, r, rnd)
        owner_w = b.owner[rows, jnp.clip(pos, 0, s_cap)]
        wslot = witness_row(wslot, r, ws, valid_w & newly[wsx] & owner_w)
        rnd_chain = jnp.where(live_chain, rnd[cex], -1)
        pos = ((rnd_chain >= 0) & (rnd_chain <= r)).sum(-1, dtype=I32)
        return rnd, pos, wslot, D.sum(dtype=I32), it

    # each event's other parent as (owner column, window position on
    # it); an evicted or absent parent reads column B, whose threshold
    # is +inf
    op_col = b.ebr[opx]
    op_q = b.eseq[opx] - b.s_off[jnp.clip(op_col, 0, B - 1)]
    cols = jnp.arange(B + 1, dtype=I32)
    # a column owns a suffix of its chain view (the prefix it shares
    # with the branch it forked from is that branch's); read so, the
    # loop leaves b.ebr alone, and the compiler keeps it in the chip's
    # fast memory for the la scan
    own_lo = b.cnt - b.owner.sum(-1, dtype=I32)

    def round_step(carry):
        r, rnd, pos, wslot, n_un, steps, fallbacks = carry
        # the band: positions pos .. pos + W - 1 of every branch, and its
        # first position's event the branch's candidate witness
        bpos = pos[:, None] + jnp.arange(W, dtype=I32)         # [B, W]
        live = bpos < b.cnt[:, None]
        bx = jnp.where(live, b.ce[rows[:, None], jnp.clip(bpos, 0, s_cap)],
                       cfg.e_cap)
        valid_w, ws = live[:, 0], bx[:, 0]
        hw = jnp.where(valid_w[:, None], helper[ws], INT32_MAX)  # [B, B]
        rb = rnd[bx]
        un = live & (rb < 0)
        # strongly seeing a supermajority, or seeded above r; a parent
        # above r lifts through the thresholds below
        hi0 = (un & ss_ok(la[bx], det[bx], hw, valid_w)) | (live & (rb > r))
        b_ocol, b_oq = op_col[bx], op_q[bx]

        def first_hi(hi):
            return jnp.min(jnp.where(hi, bpos, b.cnt[:, None]), axis=1)

        def cl_body(c):
            t, _, it = c
            # t at the other parent's column, as a select: a gather of so
            # small an operand is a kernel of its own on the chip
            t_col = jnp.concatenate([t, jnp.full((1,), INT32_MAX, I32)])
            t_op = jnp.max(jnp.where(b_ocol[..., None] == cols, t_col, -1),
                           axis=-1)
            t2 = first_hi(hi0 | (un & (b_oq >= t_op)))
            return t2, (t2 != t).any(), it + 1

        t, _, it = jax.lax.while_loop(
            lambda c: c[1], cl_body, (first_hi(hi0), jnp.asarray(True),
                                      steps)
        )
        # a branch with positions past its band and no round > r in it
        ran_out = ((t == b.cnt) & (pos + W < b.cnt)).any()

        def band_round(_):
            newly = un & (bpos < t[:, None])
            owned = newly & (bpos >= own_lo[:, None])
            rnd2 = rnd.at[jnp.where(newly, bx, e1)].set(r, mode="drop")
            wslot2 = witness_row(wslot, r, ws, owned[:, 0])
            return (rnd2, t, wslot2, n_un - owned.sum(dtype=I32), it,
                    fallbacks)

        def fallback(_):
            return full_round(r, rnd, wslot, steps) + (fallbacks + 1,)

        rnd, pos, wslot, n_un, steps, fallbacks = jax.lax.cond(
            ran_out, fallback, band_round, None)
        return r + 1, rnd, pos, wslot, n_un, steps, fallbacks

    def cond(carry):
        r, n_un = carry[0], carry[4]
        # rounds 0..r_cap-1 are assignable (wslot rows 0..r_cap-1, same
        # as the level scan); `r < r_cap - 1` here was an off-by-one that
        # silently dropped the top round at tight capacities
        return (n_un > 0) & (r < r_cap)

    zero = jnp.asarray(0, I32)
    _, rnd, _, wslot, _, closure_steps, band_fallbacks = jax.lax.while_loop(
        cond, round_step,
        (zero, rnd0, jnp.zeros(B, I32), wslot0,
         (valid_e & ~seeded).sum(dtype=I32), zero, zero),
    )

    # a fresh buffer, not the loop's carry: the compiler then keeps it in
    # the chip's fast memory for order's loop, which reads it every round
    rnd = jnp.where(valid_e, rnd, -1)
    wit = valid_e & ((b.sp < 0) | (rnd > rnd[spx]))
    wit = jnp.where(b.wseed >= 0, b.wseed == 1, wit) & valid_e
    max_round = jnp.max(jnp.where(valid_e, rnd, -1))
    return rnd, wit, wslot, max_round, closure_steps, band_fallbacks


def _rounds_scan(cfg: ForkConfig, b: ForkBatch, la: jnp.ndarray,
                 det: jnp.ndarray, helper: jnp.ndarray):
    """Round assignment level scan (branch-witness tables)."""
    n, k, B, sm, r_cap = cfg.n, cfg.k, cfg.b, cfg.super_majority, cfg.r_cap
    e1 = cfg.e_cap + 1

    rnd0 = jnp.full((e1,), -1, I32)
    wit0 = jnp.zeros((e1,), bool)
    wslot0 = jnp.full((r_cap + 1, B), -1, I32)

    def step(carry, idx):
        rnd, wit, wslot, max_round = carry
        real = idx >= 0
        idx_s = sanitize(idx, cfg.e_cap)
        spx = sanitize(b.sp[idx_s], cfg.e_cap)
        opx = sanitize(b.op[idx_s], cfg.e_cap)
        is_root = (b.sp[idx_s] < 0) & (b.op[idx_s] < 0)
        pr = jnp.maximum(rnd[spx], rnd[opx])
        pr = jnp.where(is_root, 0, pr)

        wsl = wslot[jnp.clip(pr, 0, r_cap)]                   # [Bt, B]
        valid_w = wsl >= 0
        hw = helper[sanitize(wsl, cfg.e_cap)]                 # [Bt, B, B]
        hw = jnp.where(valid_w[:, :, None], hw, INT32_MAX)
        la_x = la[idx_s]                                      # [Bt, B]
        det_x = det[idx_s]                                    # [Bt, N]
        ss_cnt = _ss_counts(
            cfg, la_x[:, None, :], det_x[:, None, :], hw
        )                                                     # [Bt, B]
        ss = (ss_cnt >= sm) & valid_w
        # witness creators strongly seen (dedupe branch columns; strided
        # OR instead of reshape+any — see _ss_counts)
        ss_c = ss[..., 0::k]
        for kk in range(1, k):
            ss_c = ss_c | ss[..., kk::k]                      # [Bt, N]
        inc = ss_c.sum(-1) >= sm
        r_x = pr + inc.astype(I32)
        w_x = (b.sp[idx_s] < 0) | (r_x > rnd[spx])

        rnd = rnd.at[idx_s].set(jnp.where(real, r_x, -1))
        wit = wit.at[idx_s].set(w_x & real)
        w_row = jnp.where(w_x & real, r_x, r_cap)
        w_col = jnp.clip(b.ebr[idx_s], 0, B - 1)
        wslot = wslot.at[w_row, w_col].set(idx_s)
        max_round = jnp.maximum(
            max_round, jnp.max(jnp.where(real, r_x, -1))
        )
        return (rnd, wit, wslot, max_round), None

    (rnd, wit, wslot, max_round), _ = jax.lax.scan(
        step, (rnd0, wit0, wslot0, jnp.asarray(-1, I32)), b.sched
    )
    # restore dump row/sentinels (SPMD-safe selects, see _la_scan)
    r_row = (jnp.arange(r_cap + 1) == r_cap)[:, None]
    e_row = jnp.arange(cfg.e_cap + 1) == cfg.e_cap
    wslot = set_sentinel(wslot, r_row, -1)
    rnd = set_sentinel(rnd, e_row, -1)
    wit = set_sentinel(wit, e_row, False)
    return rnd, wit, wslot, max_round


def _fame(cfg: ForkConfig, b: ForkBatch, la: jnp.ndarray, det: jnp.ndarray,
          helper: jnp.ndarray, wslot: jnp.ndarray, max_round: jnp.ndarray):
    """Virtual voting over branch witnesses (diagonal scan, fame.py
    pattern).  Baird's strongly-seeing lemma keeps vote tallies per-creator
    unique, so summing over branch columns never double-counts."""
    n, k, B, sm, R = cfg.n, cfg.k, cfg.b, cfg.super_majority, cfg.r_cap

    wsl = wslot[:R]                                           # [R, B]
    valid_w = wsl >= 0
    ws = sanitize(wsl, cfg.e_cap)
    law = la[ws]                                              # [R, B, B]
    detw = det[ws]                                            # [R, B, N]
    hw = jnp.where(valid_w[:, :, None], helper[ws], INT32_MAX)
    seqw = jnp.where(valid_w, b.eseq[ws], INT32_MAX)          # [R, B]
    brw = jnp.clip(b.ebr[ws], 0, B - 1)                       # [R, B]
    crw = jnp.clip(b.ecr[ws], 0, n - 1)                       # [R, B]
    mbw = b.mbit[ws]

    law_next = jnp.concatenate([law[1:], jnp.full((1, B, B), -1, I32)], 0)
    detw_next = jnp.concatenate([detw[1:], jnp.zeros((1, B, n), bool)], 0)
    valid_next = jnp.concatenate([valid_w[1:], jnp.zeros((1, B), bool)], 0)

    # ss_next[r, a, w]: round r+1 witness a strongly sees round r witness
    # w.  With _ss_counts' strided-OR formulation the whole
    # compare->or->mask->reduce chain fuses (the old reshape+any
    # materialized a 68 GB [R, A, W, N] pred at B=2048 and needed a
    # lax.map chunking workaround).
    ss_cnt = _ss_counts(
        cfg, law_next[:, :, None, :], detw_next[:, :, None, :],
        hw[:, None, :, :],
    )                                                         # [R, A, W]
    ss_next = (
        (ss_cnt >= sm) & valid_next[:, :, None] & valid_w[:, None, :]
    ).astype(F32)
    tot_next = ss_next.sum(-1)

    # see_next[r, a, x]: direct votes — a sees x
    la_ax = jnp.take_along_axis(
        law_next[:, :, :], brw[:, None, :], axis=2
    )                                                         # [R, Ba, Bx]
    det_ax = jnp.take_along_axis(
        detw_next, crw[:, None, :], axis=2
    )                                                         # [R, Ba, Bx]
    see_next = (
        (la_ax >= seqw[:, None, :]) & ~det_ax
        & valid_next[:, :, None] & valid_w[:, None, :]
    ).astype(F32)

    zpad3 = jnp.zeros((R, B, B), F32)
    ss_pad = jnp.concatenate([ss_next, zpad3], axis=0)
    tot_pad = jnp.concatenate([tot_next, jnp.zeros((R, B), F32)], axis=0)
    mb_pad = jnp.concatenate([mbw, jnp.zeros((R, B), bool)], axis=0)

    i_idx = jnp.arange(R, dtype=I32)
    in_window = i_idx < max_round

    def step(d, carry):
        votes, famous, steps = carry
        d = jnp.asarray(d, I32)
        can_vote = (i_idx + d) <= max_round
        z = jnp.zeros((), I32)
        ss_d = jax.lax.dynamic_slice(ss_pad, (d - 1, z, z), (R, B, B))
        tot_d = jax.lax.dynamic_slice(tot_pad, (d - 1, z), (R, B))
        mb_d = jax.lax.dynamic_slice(mb_pad, (d, z), (R, B))

        yays = jnp.einsum("iyw,iwx->iyx", ss_d, votes,
                          preferred_element_type=F32)
        nays = tot_d[:, :, None] - yays
        v = yays >= nays
        t = jnp.maximum(yays, nays)
        strong = t >= sm

        undecided = (famous == FAME_UNDEFINED) & valid_w & in_window[:, None]
        normal = (d % cfg.n) != 0
        deciding = strong & normal & can_vote[:, None, None]
        decide_x = deciding.any(axis=1)
        v_star = (deciding & v).any(axis=1)
        famous = jnp.where(
            undecided & decide_x,
            jnp.where(v_star, FAME_TRUE, FAME_FALSE).astype(jnp.int8),
            famous,
        )
        coin_vote = jnp.where(strong, v, mb_d[:, :, None])
        new_votes = jnp.where(normal, v, coin_vote).astype(F32)
        votes = jnp.where(can_vote[:, None, None], new_votes, votes)
        return votes, famous, steps + 1

    d_max = jnp.maximum(max_round, 2)
    votes, famous, vote_steps = jax.lax.fori_loop(
        2, d_max + 1, step,
        (see_next, jnp.zeros((R, B), jnp.int8), jnp.asarray(0, I32)),
    )

    decided_round = ((~valid_w) | (famous != FAME_UNDEFINED)).all(axis=1)
    has_w = valid_w.any(axis=1)
    cand = in_window & decided_round & has_w
    lcr = jnp.max(jnp.where(cand, i_idx, -1))
    famous_full = jnp.zeros((R + 1, B), jnp.int8).at[:R].set(famous)
    return famous_full, lcr, vote_steps


def _order(cfg: ForkConfig, b: ForkBatch, fd: jnp.ndarray,
           first_det: jnp.ndarray, wslot: jnp.ndarray,
           famous: jnp.ndarray, rnd: jnp.ndarray, max_round: jnp.ndarray):
    """Round received + median consensus timestamps (order.py pattern,
    fork-aware sees)."""
    n, B, R, e1 = cfg.n, cfg.b, cfg.r_cap, cfg.e_cap + 1

    wsl = wslot[:R]
    valid_w = wsl >= 0
    ws = sanitize(wsl, cfg.e_cap)
    seqw = jnp.where(valid_w, b.eseq[ws], -1)                 # [R, B]
    fam = (famous[:R] == FAME_TRUE) & valid_w
    decided = ((~valid_w) | (famous[:R] != FAME_UNDEFINED)).all(axis=1)
    has_w = valid_w.any(axis=1)
    fam_cnt = fam.sum(axis=1)

    valid_e = (jnp.arange(e1) < b.n_events) & (b.eseq >= 0)
    # sees[x, br-witness]: witness at (br, seqw) sees x
    fdet_x = first_det.T[jnp.clip(b.ecr, 0, n - 1)]           # [E+1, B]

    def step(i, rr):
        active = decided[i] & has_w[i] & (i <= max_round)
        sees = fam[i][None, :] & (fd <= seqw[i][None, :]) \
            & (seqw[i][None, :] < fdet_x)                     # [E+1, B]
        c = sees.sum(axis=1)
        cond = (
            valid_e & (rr == -1) & (i > rnd) & active
            & (c > fam_cnt[i] // 2)
        )
        return jnp.where(cond, i, rr)

    rr = jax.lax.fori_loop(1, R, step, jnp.full((e1,), -1, I32))
    newly = valid_e & (rr != -1)

    i_of = jnp.clip(rr, 0, R - 1)
    fam_i = fam[i_of]
    seqw_i = seqw[i_of]
    sees_i = fam_i & (fd <= seqw_i) & (seqw_i < fdet_x)       # [E+1, B]

    # tv[x, br] = ts of chain-br's event at index fd[x, br] (the oldest
    # self-ancestor of that branch's witness to see x); the ts grid is
    # positional, so absolute fd indexes shift by the window offset
    ts_grid = b.ts[sanitize(b.ce, cfg.e_cap)]                 # i64[B, S+1]
    fdc = jnp.clip(fd - b.s_off[None, :], 0, cfg.s_cap)
    INT64_MAX = jnp.iinfo(jnp.int64).max

    def acc_step(s, acc):
        return jnp.where(fdc == s, ts_grid[:, s][None, :], acc)

    tv = jax.lax.fori_loop(
        0, cfg.s_cap + 1, acc_step,
        jnp.full((e1, B), INT64_MAX, dtype=b.ts.dtype),
    )
    tv = jnp.where(sees_i, tv, INT64_MAX)
    tv_sorted = jnp.sort(tv, axis=1)
    cnt_s = sees_i.sum(axis=1)
    med = tv_sorted[jnp.arange(e1), jnp.clip(cnt_s // 2, 0, B - 1)]
    cts = jnp.where(newly, med, 0)
    return rr, cts


def fork_pipeline_impl(cfg: ForkConfig, b: ForkBatch) -> ForkOut:
    """The whole fork-aware pipeline over one batch.  Its phases run
    under the fused step's ``named_scope`` names (parallel/sharded.py
    ``consensus_step_impl``): ``babble_ingest`` with ``la`` / ``fd`` /
    ``rounds`` children (fork detection and the see-interval helper are
    ingest's own), ``babble_fame`` and ``babble_order``."""
    # shared measured cost model (state.fd_reverse_scan_wins); the fork
    # chain-view count is k^2 heavier than the honest one it was fit to
    from .state import fd_reverse_scan_wins

    with jax.named_scope("babble_ingest"):
        with jax.named_scope("la"):
            la = _la_scan(cfg, b)
        det = _detect(cfg, b, la)
        first_det = _first_det(cfg, b, det)
        with jax.named_scope("fd"):
            if fd_reverse_scan_wins(b.sched.shape[0], cfg.e_cap, cfg.k):
                fd = _fd_reverse(cfg, b)
            else:
                fd = _fd_chains(cfg, b, la)
        helper = _helper(cfg, b, fd, first_det)
        with jax.named_scope("rounds"):
            (rnd, wit, wslot, max_round, closure_steps,
             band_fallbacks) = _rounds_closure(cfg, b, la, det, helper)
    with jax.named_scope("babble_fame"):
        famous, lcr, vote_steps = _fame(cfg, b, la, det, helper, wslot,
                                        max_round)
    with jax.named_scope("babble_order"):
        rr, cts = _order(cfg, b, fd, first_det, wslot, famous, rnd,
                         max_round)
    return ForkOut(
        la=la, det=det, fd=fd, round=rnd, witness=wit, wslot=wslot,
        famous=famous, rr=rr, cts=cts, max_round=max_round, lcr=lcr,
        closure_steps=closure_steps, vote_steps=vote_steps,
        band_fallbacks=band_fallbacks,
    )


fork_pipeline = jax.jit(fork_pipeline_impl, static_argnums=(0,))
