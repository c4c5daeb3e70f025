"""TpuHashgraph: the TPU-native consensus engine.

Host/device split:
- Host (``core.dag.HostDag``): hash<->slot index, signature + fork
  validation, wire conversion, level scheduling, final sort + commit.
- Device (``ops.*``): dense coordinate tensors and the jitted pipeline —
  ingest (coordinates + rounds), decide_fame (vote matmuls), decide_order
  (round-received + median timestamps).

API mirrors the reference Hashgraph (hashgraph/hashgraph.go) and the
pure-Python oracle so the two engines are drop-in interchangeable:
insert_event / divide_rounds / decide_fame / find_order / run_consensus,
plus the predicate surface (ancestor, strongly_see, round, witness, ...)
used by tests and the node runtime.

Batching: insert_event only indexes host-side; device ingestion happens
lazily at the next consensus call (or explicit flush), so a gossip sync's
worth of events rides one kernel launch.  Shapes are bucketed to powers of
two to bound recompilation.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..common import OffsetList
from ..core.dag import HostDag, InsertError
from ..core.event import Event, WireEvent
from .digest import CommitDigest
from ..ops import fame as fame_ops
from ..ops import flush as flush_ops
from ..ops import ingest as ingest_ops
from ..ops import order as order_ops
from ..ops.state import (
    FAME_TRUE,
    FAME_UNDEFINED,
    HEAD_GATE_HORIZON,
    INT32_MAX,
    DagConfig,
    DagState,
    bucket,
    compact as compact_op,
    grow_state,
    init_state,
    ts32_ok,
)

_FD_FULL_THRESHOLD = 2048  # batch size above which full FD recompute wins

#: pending-batch size above which the throughput path wins over the
#: fused latency program (gossip flushes are tens of events; bulk
#: ingest/catch-up ships thousands)
LATENCY_K_MAX = 256

#: membership plane: a committed transition takes effect at decided
#: round ``round_received(tx) + EPOCH_LAG``.  Any positive lag works —
#: reception requires ancestry of the deciding round's famous
#: witnesses, so everything received at or below the boundary is held
#: by every node that reaches it — but a small cushion keeps the
#: boundary comfortably above the committing flush's own lcr jumps.
EPOCH_LAG = 2

#: pipelined membership (ROADMAP 5a): max transitions queued behind the
#: pending epoch boundary.  Mirrors membership.epoch.PIPELINE_WINDOW —
#: a transition may be stamped up to this many epochs before the one it
#: applies in, and the chain-of-custody verifier accepts exactly that
#: window, so the two bounds must agree.
MEMBERSHIP_QUEUE_MAX = 64

#: bounded membership_log (ROADMAP 5a): entries kept after truncation.
#: Older entries fold into (membership_base_epoch, membership_addrs);
#: a joiner whose trusted base predates the retained window must
#: bootstrap from a fresher base (same contract as the rolling event
#: window's TooLate).
MEMBERSHIP_LOG_KEEP = 256

_bucket = bucket


class TpuHashgraph:
    #: this engine supports the latency/throughput kernel split (the
    #: fused live-flush program).  Subclasses with their own memory
    #: layout (WideHashgraph: blocked la/fd, no fused coordinate
    #: tensors) set this False and pin kernel_class via class attrs —
    #: they inherit run_consensus_timed but always take the
    #: three-phase branch through their own overrides.
    KERNEL_SPLIT = True
    # class-level defaults so subclasses that skip __init__ (the wide
    # engine allocates its own state) still satisfy the dispatcher
    finality_gate = False
    kernel_class = "throughput"
    last_kernel_class: Optional[str] = None
    flush_fallbacks = 0
    # kernel working-set diet (ROADMAP item 4) defaults for subclasses
    # that allocate their own state: frontier sizing only runs on the
    # fused latency path, but the mirrors must exist
    frontier = True
    _frontier_cache = 0
    #: attribution plane (ISSUE 11): per-flush HBM bytes-touched
    #: estimate ({"ingest","fame","order","total"}, ops/flush.py) and
    #: the per-phase wall timings of the last probed flush.  Read by
    #: the node after each consensus run; None when nothing flushed.
    last_flush_bytes: Optional[dict] = None
    #: phase probe (Config.phase_probe): dispatch the fused latency
    #: flush as three separately-timed sub-programs instead of one
    #: launch — bit-identical results, one host sync per phase
    phase_probe = False
    _last_phase_timings: Optional[dict] = None
    inactive_rounds: Optional[int] = None
    _evicted_creators_cache = 0
    # membership plane (ISSUE 9) class-level defaults: engines without
    # epoch-transition support (wide/fork override nothing — committed
    # membership txs are inert data there) still expose the epoch
    # surface checkpoints/snapshots/metrics read
    epoch = 0
    pending_membership: Optional[dict] = None
    membership_log: tuple = ()
    membership_rejects = 0
    # pipelined transitions (ROADMAP 5a): transitions committed while
    # one is pending queue FIFO instead of being dropped
    membership_queue: tuple = ()
    # bounded membership_log: epoch of the newest truncated entry (a
    # verifier whose trusted base is older cannot bridge the chain and
    # must bootstrap from a fresher base), plus the gossip addresses of
    # members whose join entries were truncated
    membership_base_epoch = 0
    membership_addrs: dict = {}

    def __init__(
        self,
        participants: Dict[str, int],
        commit_callback: Optional[Callable[[List[Event]], None]] = None,
        verify_signatures: bool = True,
        e_cap: int = 4096,
        s_cap: int = 1024,
        r_cap: int = 64,
        auto_compact: bool = False,
        seq_window: int = 256,
        round_margin: int = 2,
        compact_min: Optional[int] = None,
        consensus_window: Optional[int] = None,
        finality_gate: bool = False,
        ts32: bool = False,
        kernel_class: str = "auto",
        inactive_rounds: Optional[int] = 32,
        packed_votes: bool = True,
        frontier: bool = True,
    ):
        n = len(participants)
        self.participants = participants
        self.commit_callback = commit_callback
        self.dag = HostDag(participants, verify_signatures=verify_signatures)
        # kernel working-set diet (ROADMAP item 4): packed_votes rides
        # the DagConfig (it selects kernel math inside the compiled
        # programs), frontier is engine policy (it only sizes the F
        # bucket the order phase scans).  Both are bit-parity-preserving
        # — False pins the pre-diet kernels for differential tests.
        self.cfg = DagConfig(n=n, e_cap=e_cap, s_cap=s_cap, r_cap=r_cap,
                             ts32=ts32, packed=packed_votes)
        self.state: DagState = init_state(self.cfg)
        self.frontier = frontier
        # host mirror of the reception frontier: a monotone LOWER bound
        # on the first live slot with rr undecided (rr values are
        # sticky, so last flush's first-undecided row can only move up;
        # epoch transitions reset decisions and reset this to 0).  The
        # kernel derives the exact slice offset in-device — the mirror
        # only sizes the static F bucket, and under-counting it is safe
        # (a bigger slice), while over-counting would skip receptions.
        self._frontier_cache = 0

        # Streaming incremental engine (ROADMAP item 3):
        # - finality_gate: witness-set finality (ops/wide.py complete=False
        #   ported to the fused path) — a round's fame decides only once
        #   every chain's head round has passed it, so prn whitening and
        #   cts medians freeze on the same witness set fleet-wide.  The
        #   live Core turns this on; whole-DAG batch paths keep the
        #   ungated reference semantics.
        # - kernel_class: "auto" picks the fused small-batch latency
        #   program (ops/flush.live_flush) for gossip-sized flushes and
        #   the legacy throughput phases for bulk ingest; "latency" /
        #   "throughput" pin one path (parity tests, benches).
        # - ts32: i32 relative timestamps in the order median (the span
        #   guard below enforces ops.state.ts32_ok host-side).
        if kernel_class not in ("auto", "latency", "throughput"):
            raise ValueError(f"unknown kernel_class {kernel_class!r}")
        self.finality_gate = finality_gate
        self.kernel_class = kernel_class
        self.last_kernel_class: Optional[str] = None
        self._max_round_cache = -1        # host mirror of state.max_round
        self._ts_lo: Optional[int] = None  # ts32 span guard mirrors
        self._ts_hi: Optional[int] = None
        #: AOT executable map: (W, gate, kpad, tpad, bpad) -> compiled
        #: live_flush program (ops/aot.prewarm_engine fills it from the
        #: manifest; a miss falls back to the jitted entry, which the
        #: persistent XLA cache still serves across restarts)
        self._aot: Dict[tuple, object] = {}
        self._aot_dir: Optional[str] = None
        self._aot_recorded: set = set()

        # Rolling-window policy (reference caches.go semantics; the live
        # node turns auto_compact on so memory stays bounded forever):
        # - seq_window: newest events per creator always kept (other-parent
        #   reachability for lagging peers; beyond it syncs get TooLate)
        # - round_margin: decided rounds kept below lcr (committer safety)
        # - compact_min: evictable-prefix length worth a compaction pass
        # - consensus_window: committed-log entries kept (None = all)
        self.auto_compact = auto_compact
        self.seq_window = seq_window
        self.round_margin = round_margin
        self.compact_min = compact_min if compact_min is not None else max(
            e_cap // 4, 32
        )
        self.consensus_window = consensus_window
        # Per-creator eviction (ISSUE 8): a creator whose chain head is
        # more than inactive_rounds DECIDED rounds behind lcr loses its
        # seq_window retention — its tail becomes evictable, the slot
        # prefix can advance past it, and its (index, hex) eviction
        # horizon (dag.evicted_heads) is what its eventual return
        # bootstraps against.  None disables (pre-PR behavior: one dead
        # peer pins eviction fleet-wide for the whole outage).
        self.inactive_rounds = inactive_rounds
        #: creators whose whole retained window has been evicted (the
        #: babble_evicted_creators gauge; maintained by maybe_compact)
        self._evicted_creators_cache = 0
        #: flushes where the latency window could not cover the
        #: undecided round span (babble_flush_fallbacks_total): either
        #: deferred in place because a stalled finality gate makes the
        #: uncovered rounds undecidable anyway, or degraded to the
        #: throughput surface for run-to-completion
        self.flush_fallbacks = 0
        self._fallback_counted = False   # per-flush dedup for the gauge
        # attribution plane (ISSUE 11): per-flush traffic estimate +
        # the phase-probe timings of the last latency flush
        self.last_flush_bytes: Optional[dict] = None
        self._last_phase_timings: Optional[dict] = None
        self.phase_probe = False

        # Membership plane (ISSUE 9): the validator set is consensus
        # state.  A committed, subject-signed transition tx schedules a
        # transition at decided-round boundary rr + EPOCH_LAG; commits
        # past the boundary are HELD until the engine re-shapes
        # (apply_epoch_transition) and re-decides them under the new
        # peer set.  membership_log is the chain of custody a joiner's
        # fast-forward verifies (membership/epoch.py).
        self.epoch = 0
        self.pending_membership: Optional[dict] = None
        self.membership_log: List[dict] = []
        self.membership_rejects = 0
        #: transitions committed while one is pending, FIFO-applied at
        #: successive epoch boundaries (pipelined membership): a fleet
        #: onboarding 50 validators no longer resubmits 49 times
        self.membership_queue: List[dict] = []
        #: bounded membership_log: base epoch + truncated-join addrs
        self.membership_log_keep = MEMBERSHIP_LOG_KEEP
        self.membership_base_epoch = 0
        self.membership_addrs: Dict[str, str] = {}

        self.consensus = OffsetList()             # hex ids in consensus order
        #: rolling hash chain over the committed order — the attestable
        #: frontier signed fast-forward proofs are built on (digest.py)
        self._digest = CommitDigest()
        self.consensus_transactions = 0
        self.last_committed_round_events = 0
        self._received: set = set()               # global slots already ordered
        self._ordered_total = 0                   # |_received| incl. evicted
        self._view: Dict[str, np.ndarray] = {}    # host cache of device arrays
        self._lcr_cache = -1                      # host mirror for lock-free stats
        self._r_off = 0                           # host mirror of state.r_off

    # ------------------------------------------------------------------
    # properties mirroring the oracle/reference

    @property
    def n(self) -> int:
        return self.cfg.n

    def super_majority(self) -> int:
        return self.cfg.super_majority

    @property
    def last_consensus_round(self) -> Optional[int]:
        self.flush()
        lcr = int(self.state.lcr)
        self._lcr_cache = lcr
        return None if lcr < 0 else lcr

    @property
    def undetermined_count(self) -> int:
        self.flush()
        return self.dag.n_events - self._ordered_total

    def stats_snapshot(self) -> Dict[str, int]:
        """Lock-free stats from host-side mirrors — safe to call from the
        stats endpoint while another thread drives the device pipeline
        (no flush, no device reads)."""
        return {
            "last_consensus_round": self._lcr_cache,
            "undetermined_events": self.dag.n_events - self._ordered_total,
            "consensus_events": len(self.consensus),
            "consensus_transactions": self.consensus_transactions,
            "last_committed_round_events": self.last_committed_round_events,
            # rolling-window gauges: total history vs what's actually held
            "evicted_events": self.dag.slot_base,
            "live_window": self.dag.n_events - self.dag.slot_base,
            # creators whose retained tail was evicted for inactivity
            # (their return must bootstrap through verified fast-forward)
            "evicted_creators": self._evicted_creators_cache,
            # membership plane: current epoch + transitions applied
            # (== epoch even after the bounded log truncates entries)
            "epoch": self.epoch,
            "membership_transitions": self.epoch,
        }

    # ------------------------------------------------------------------
    # commit digest (verified fast-forward, store/proof.py)

    @property
    def commit_digest(self) -> str:
        """Digest over the full committed order so far (O(1) state)."""
        return self._digest.head

    @property
    def commit_length(self) -> int:
        return self._digest.length

    def commit_digest_at(self, position: int) -> Optional[str]:
        """Digest after the first ``position`` commits — the attestation
        peers answer during a joiner's fast-forward proof check; None
        when the position is ahead of us or rolled off history."""
        return self._digest.digest_at(position)

    # ------------------------------------------------------------------
    # ingestion

    def insert_event(self, event: Event) -> None:
        self.dag.insert(event)

    def _check_narrow_seq_range(self) -> None:
        """la/fd hold ABSOLUTE seqs, which compaction never rebases:
        narrow coordinates are only sound while every chain head is
        clear of the dtype's INF sentinel (batch pipelines reset per
        run; a long-lived compacting engine is not)."""
        if not (self.cfg.coord16 or self.cfg.coord8):
            return
        head = max((len(c) for c in self.dag.chains), default=0)
        if head >= int(self.cfg.fd_inf) - 1:
            raise OverflowError(
                f"narrow-coordinate engine exceeded seq range (head seq "
                f"{head}); rebuild with wider coordinates"
            )

    def flush(self) -> None:
        """Push pending host events through the device ingest pipeline."""
        if not self.dag.pending:
            return
        self._check_narrow_seq_range()
        batch, fd_mode = self.build_batch()
        self.state = ingest_ops.ingest(self.cfg, self.state, fd_mode, batch)
        self._view = {}
        # Round-capacity saturation check: if the highest assigned round is
        # at the capacity edge, witness-table writes may have clipped and
        # round increments may have been missed — grow the window and
        # recompute the suspect suffix (no full re-ingest: coordinates are
        # round-independent, and evicted history could not be replayed).
        self._max_round_cache = int(self.state.max_round)
        if self._max_round_cache - self._r_off >= self.cfg.r_cap - 1:
            self._repair_rounds()

    def _repair_rounds(self) -> None:
        """Double r_cap and recompute rounds for events whose assignment may
        have clipped.  An event's stored round can only be wrong if a parent
        round hit the witness-table edge, so the suspect set is exactly
        ``round >= r_off + old_r_cap`` (descendants of a wrong event always
        carry a stored round >= their wrong parent's, keeping the set
        closed).  Suspects are rescanned level by level against the intact
        lower witness rows."""
        base = self.dag.slot_base
        while True:
            old_r_cap = self.cfg.r_cap
            new_cfg = self.cfg._replace(r_cap=old_r_cap * 2)
            self.state = grow_state(self.state, self.cfg, new_cfg)
            self.cfg = new_cfg
            self._view = {}
            self._aot = {}   # executables were compiled for the old shapes

            rnd = self._arr("round")
            ne = self.dag.n_events - base
            sus = np.nonzero(
                rnd[:ne] >= self._r_off + old_r_cap
            )[0].astype(np.int32)
            if len(sus):
                self.state = ingest_ops.rescan_rounds(
                    self.cfg, self.state,
                    jnp.asarray(self._level_sched(sus)),
                )
                self._view = {}
            self._max_round_cache = int(self.state.max_round)
            if self._max_round_cache - self._r_off < self.cfg.r_cap - 1:
                return

    def build_batch(self):
        """Drain pending host events into a padded device EventBatch.

        Returns (batch, fd_mode).  Normally consumed by flush(); exposed so
        alternative executors (the sharded pipeline, the graft entry) can
        feed the same batches through their own jitted step.
        """
        k = len(self.dag.pending)
        self._ensure_capacity(k)
        sp, op, creator, seq, ts, mbit, sched = self.dag.take_pending()
        if self.cfg.ts32 and k:
            # span guard for the i32 relative-timestamp median: rebasing
            # is exact only while the live span fits int32 (state.ts32_ok)
            lo, hi = int(ts.min()), int(ts.max())
            self._ts_lo = lo if self._ts_lo is None else min(self._ts_lo, lo)
            self._ts_hi = hi if self._ts_hi is None else max(self._ts_hi, hi)
            if not ts32_ok(self._ts_lo, self._ts_hi):
                raise OverflowError(
                    f"ts32 engine exceeded the int32 timestamp span "
                    f"({self._ts_hi - self._ts_lo} ns): rebuild with "
                    "ts32=False (wall-clock fleets must keep i64)"
                )

        kpad = _bucket(k)
        t, b = sched.shape
        tpad, bpad = _bucket(t, 1), _bucket(b, 1)

        def pad1(a, fill, dtype):
            out = np.full(kpad, fill, dtype)
            out[:k] = a
            return out

        sched_p = np.full((tpad, bpad), -1, np.int32)
        sched_p[:t, :b] = sched

        batch = ingest_ops.EventBatch(
            sp=jnp.asarray(pad1(sp, -1, np.int32)),
            op=jnp.asarray(pad1(op, -1, np.int32)),
            creator=jnp.asarray(pad1(creator, 0, np.int32)),
            seq=jnp.asarray(pad1(seq, 0, np.int32)),
            ts=jnp.asarray(pad1(ts, 0, np.int64)),
            mbit=jnp.asarray(pad1(mbit, False, bool)),
            k=jnp.asarray(k, jnp.int32),
            sched=jnp.asarray(sched_p),
        )
        fd_mode = "full" if k > _FD_FULL_THRESHOLD else "incremental"
        return batch, fd_mode

    def _ensure_capacity(self, k_new: int) -> None:
        cfg = self.cfg
        # live (windowed) extents — capacities bound the window, not history
        need_e = self.dag.n_events - self.dag.slot_base
        max_chain = max(
            (len(c) - c.start for c in self.dag.chains), default=0
        )
        # Rounds heuristic: a level can raise the max round by at most 1,
        # but in practice a round spans several levels, so sizing r_cap by
        # level count would inflate the fame/order tensors ~4x.  Undershoot
        # is safe: flush() detects wslot saturation and repairs.
        levels_new = len({self.dag.levels[s] for s in self.dag.pending})
        need_r = (
            max(int(self.state.max_round) - self._r_off, 0)
            + 2
            + min(levels_new, max(8, levels_new // 4))
        )

        e_cap, s_cap, r_cap = cfg.e_cap, cfg.s_cap, cfg.r_cap
        while need_e > e_cap:
            e_cap *= 2
        while max_chain >= s_cap:
            s_cap *= 2
        while need_r >= r_cap:
            r_cap *= 2
        if (e_cap, s_cap, r_cap) != (cfg.e_cap, cfg.s_cap, cfg.r_cap):
            new_cfg = cfg._replace(e_cap=e_cap, s_cap=s_cap, r_cap=r_cap)
            self.state = grow_state(self.state, cfg, new_cfg)
            self.cfg = new_cfg
            self._view = {}
            self._aot = {}   # executables were compiled for the old shapes

    # ------------------------------------------------------------------
    # consensus pipeline

    def divide_rounds(self) -> None:
        # rounds are assigned during ingest; dividing == flushing
        self.flush()

    def decide_fame(self) -> None:
        self.flush()
        # batch_window=False: the live engine rolls windows, so wide-N
        # fame must use the absolute-seq compare path (fame.py docstring)
        self.state = fame_ops.decide_fame_auto(
            self.cfg, self.state, False, self.finality_gate
        )
        self._view = {}

    def find_order(self) -> List[Event]:
        self.flush()
        self.state = order_ops.decide_order(self.cfg, self.state)
        self._view = {}
        return self._collect_ordered()

    def _collect_ordered(self) -> List[Event]:
        """Host half of the order phase, shared by the throughput and
        latency kernels: read rr/cts, commit newly-received events in
        consensus_sort order, roll the window.

        Membership commit gate: while a peer-set transition is pending
        at boundary B, events received in rounds > B are HELD — not
        committed, not marked received — because their reception was
        decided under the outgoing peer set and will be re-decided
        (identically on every replica) after the epoch applies.
        Everything at or below B commits under the old set on every
        node; once lcr reaches B the transition applies in place."""
        rr = self._arr("rr")
        cts = self._arr("cts")
        base = self.dag.slot_base
        ne = self.dag.n_events - base          # live rows
        self._lcr_cache = int(self.state.lcr)
        # refresh the reception-frontier mirror (kernel diet): first
        # live row still undecided.  rr assignments are sticky, so this
        # is a monotone lower bound for every later flush — exactly the
        # safety the F bucket needs (see _frontier_f).
        und = rr[:ne] < 0
        self._frontier_cache = int(np.argmax(und)) if und.any() else int(ne)
        new_slots = [
            s for s in range(ne)
            if rr[s] >= 0 and (base + s) not in self._received
        ]
        if not new_slots:
            self._maybe_apply_membership()
            if self.auto_compact:
                self.maybe_compact()
            return []

        candidates: List[Event] = []
        for s in new_slots:
            ev = self.dag.events[base + s]
            ev.round_received = int(rr[s])
            ev.consensus_timestamp = int(cts[s])
            candidates.append(ev)

        from .ordering import consensus_sort

        candidates = consensus_sort(candidates, self._round_prn)
        new_events: List[Event] = []
        for ev in candidates:
            pend = self.pending_membership
            if pend is not None and ev.round_received > pend["boundary"]:
                # held: re-received and committed by the next epoch
                continue
            new_events.append(ev)
            self._received.add(self.dag.slot_of[ev.hex()])
            self.consensus.append(ev.hex())
            self._digest.note(ev.hex())
            self.consensus_transactions += len(ev.transactions)
            self._maybe_schedule_membership(ev)
        self._ordered_total += len(new_events)

        lcr = int(self.state.lcr)
        self._lcr_cache = lcr
        if lcr >= 1:
            rounds = self._arr("round")
            self.last_committed_round_events = int(
                np.count_nonzero(rounds[:ne] == lcr - 1)
            )

        if self.commit_callback is not None and new_events:
            self.commit_callback(new_events)
        self._maybe_apply_membership()
        if self.auto_compact:
            self.maybe_compact()
        return new_events

    def run_consensus(self) -> List[Event]:
        events, _ = self.run_consensus_timed()
        return events

    def run_consensus_timed(self) -> Tuple[List[Event], Dict[str, float]]:
        """One full consensus pass, dispatched per flush between the two
        compiled surfaces (the tentpole's kernel split):

        - **latency** — the fused ops/flush.live_flush program (one
          launch: incremental ingest + W-round windowed fame/order over
          persisted frontiers) for gossip-sized flushes; shape-bucketed
          so a live stream shares one program.
        - **throughput** — the legacy three-phase surface (full-table
          fame, all-rounds order, batch fd strategies) for bulk
          ingest/catch-up and any shape the window can't cover.

        Both paths are bit-identical on the same flush sequence
        (tests/test_flush.py parity suite); ``last_kernel_class``
        records the pick for the node's flush histograms.

        Profiling hooks (ISSUE 11 (c)): each dispatch runs inside a
        ``jax.profiler.TraceAnnotation`` region (nanosecond-cheap when
        no trace is active, phase-attributed in a /debug/trace
        capture), and ``last_flush_bytes`` carries the flush's
        HBM-traffic estimate for the node's bytes histograms."""
        from jax.profiler import TraceAnnotation

        k_pending = len(self.dag.pending)
        t0 = time.perf_counter()
        if self._latency_ok():
            # _flush_live overwrites this with "throughput" when it
            # internally degrades to the full-table phases (round
            # repair, W undershoot) — the flush histogram must not
            # book multi-second full-table passes under "latency"
            self.last_kernel_class = "latency"
            with TraceAnnotation("babble_flush_latency"):
                events = self._flush_live()
            out = {"flush_s": time.perf_counter() - t0}
            if self._last_phase_timings:
                out.update(self._last_phase_timings)
            return events, out
        self.last_kernel_class = "throughput"
        with TraceAnnotation("babble_flush_ingest"):
            self.divide_rounds()
        t1 = time.perf_counter()
        with TraceAnnotation("babble_flush_fame"):
            self.decide_fame()
        t2 = time.perf_counter()
        with TraceAnnotation("babble_flush_order"):
            events = self.find_order()
        t3 = time.perf_counter()
        if type(self).KERNEL_SPLIT and k_pending:
            self.last_flush_bytes = flush_ops.throughput_bytes_estimate(
                self.cfg, k_pending
            )
        return events, {
            "divide_rounds_s": t1 - t0,
            "decide_fame_s": t2 - t1,
            "find_order_s": t3 - t2,
        }

    def _latency_ok(self) -> bool:
        """Host-mirror-only check (no device sync) that the fused
        latency program can cover this flush exactly."""
        if self.kernel_class == "throughput":
            return False
        k = len(self.dag.pending)
        if self.kernel_class == "auto" and k > LATENCY_K_MAX:
            return False
        # the windowed median runs unchunked: past the chunk threshold
        # the throughput path's blocked median must take over
        if (self.cfg.e_cap + 1) * self.cfg.n > order_ops.MEDIAN_CHUNK_THRESHOLD:
            return False
        # open rounds the window must cover: the undecided span plus
        # what this batch can add.  A topological level raises max_round
        # by at most 1 but a round spans several levels in practice
        # (same ~4:1 heuristic as _ensure_capacity); underestimating is
        # SAFE — rounds past the window top simply defer to the next
        # flush, whose estimate sees the updated max_round mirror —
        # while the old levels-as-rounds estimate pushed routine gossip
        # flushes onto the throughput surface for nothing
        levels_new = len({self.dag.levels[s] for s in self.dag.pending})
        est = (
            self._max_round_cache - max(self._lcr_cache, -1)
            + max(2, levels_new // 4 + 1)
        )
        if self.finality_gate and est > HEAD_GATE_HORIZON + 2:
            # Stall fallback (PR 7 leftover d): a stalled finality gate
            # (all peers down K rounds: the lone live chain piles up
            # LEVELS without advancing rounds, and deep undecided spans
            # survive the staleness horizon) inflated the raw span
            # estimate past every W bucket, silently forcing each flush
            # onto the expensive throughput surface for the whole
            # outage.  Rounds beyond head_round_min + 1 cannot decide
            # while the gate stalls, so a window of the staleness
            # horizon is all fame/order can use — cap the estimate
            # there (counted on babble_flush_fallbacks_total) and let
            # _flush_live's undershoot check (which consults the host
            # head-round minimum) degrade only when the gap is NOT
            # gate-explained.
            self.flush_fallbacks += 1
            self._fallback_counted = True
            est = HEAD_GATE_HORIZON + 2
        else:
            self._fallback_counted = False
        w = flush_ops.bucket_w(max(est, 1), self.cfg.r_cap)
        if w == 0:
            return False
        # the window slice must fit below the round-capacity edge with
        # saturation headroom (the throughput path owns round repair)
        top = max(self._lcr_cache + 1, 0) - self._r_off + w
        if top > self.cfg.r_cap - 1:
            return False
        if self._max_round_cache + levels_new - self._r_off \
                >= self.cfg.r_cap - 2:
            return False
        self._latency_w = w
        return True

    def _frontier_f(self) -> int:
        """Static frontier bucket for this flush (kernel working-set
        diet): a power-of-two cover of the live frontier height — every
        event row from the first undecided slot (host lower-bound
        mirror) through the window top, pending batch included.  The
        frontier=False pin (and any height past the last bucket)
        returns full height e1, the pre-diet behavior."""
        e1 = self.cfg.e_cap + 1
        if not self.frontier:
            return e1
        live = self.dag.n_events - self.dag.slot_base
        f = flush_ops.bucket_f(live - self._frontier_cache, e1)
        self._last_frontier_f = f
        return f

    def _flush_live(self) -> List[Event]:
        """One fused latency flush: build the (possibly empty) bucketed
        batch, run live_flush with donated state (AOT executable when
        prewarmed, jit otherwise), refresh host mirrors, commit."""
        self._check_narrow_seq_range()
        w = self._latency_w
        k_pending = len(self.dag.pending)
        batch, _ = self.build_batch()
        # the frontier bucket must be sized AFTER build_batch: its
        # _ensure_capacity may have grown e_cap, and bucket_f clamps
        # against e1 — sized before growth, a growth flush could pick
        # an F below the live undecided span and silently skip
        # receptions (the exactly-once property cuts both ways)
        f = self._frontier_f()
        key = (w, f, self.finality_gate, batch.sp.shape[0]) \
            + batch.sched.shape
        exe = self._aot.get(key)
        self._last_phase_timings = None
        if self.phase_probe:
            # three timed dispatches, bit-identical to the fused launch
            # (same impls, same order) — the per-phase wall meter
            self.state, self._last_phase_timings = flush_ops.probed_flush(
                self.cfg, w, f, self.finality_gate, self.state, batch
            )
        elif exe is not None:
            self.state = exe(self.state, batch)
        else:
            self.state = flush_ops.live_flush(
                self.cfg, w, f, self.finality_gate, self.state, batch
            )
            if self._aot_dir is not None and key not in self._aot_recorded:
                # record the shape so the next restart can AOT-compile it
                # against the persistent cache before the first flush
                from ..ops import aot as aot_ops

                self._aot_recorded.add(key)
                aot_ops.record_shape(self._aot_dir, self.cfg, key)
        self.last_flush_bytes = flush_ops.flush_bytes_estimate(
            self.cfg, w, k_pending, f
        )
        self._view = {}
        lcr_pre = self._lcr_cache
        self._max_round_cache = int(self.state.max_round)
        if self._max_round_cache - self._r_off >= self.cfg.r_cap - 1:
            # headroom check should make this unreachable; degrade to the
            # repairing throughput path rather than trust clipped rounds
            self.last_kernel_class = "throughput"
            self._book_fallback_bytes()
            self._repair_rounds()
            self.decide_fame()
            return self.find_order()
        if self._max_round_cache > max(lcr_pre, -1) + w:
            if not getattr(self, "_fallback_counted", False):
                # one fallback event per flush: the estimate cap in
                # _latency_ok may already have counted this one
                self.flush_fallbacks += 1
            if (self.finality_gate
                    and self._head_round_min_host() <= max(lcr_pre, -1) + w):
                # stalled finality gate: every round above the window
                # top is beyond the head-round minimum, so fame could
                # not decide it on ANY surface this flush — deferring
                # in place is run-to-completion, and staying on the
                # latency kernel is exactly the point of the bounded
                # window (babble_flush_fallbacks_total counts these)
                return self._collect_ordered()
            # the W estimate undershot (stale mirrors after a checkpoint
            # restore, or a batch that raised rounds faster than the
            # levels heuristic): rounds above the window top got no
            # votes this pass.  run_consensus is run-to-completion, so
            # finish with the full-table phases instead of deferring to
            # a flush that may never come.
            self.last_kernel_class = "throughput"
            self._book_fallback_bytes()
            self.decide_fame()
            return self.find_order()
        return self._collect_ordered()

    def _book_fallback_bytes(self) -> None:
        """A latency flush degrading to the full-table phases touches
        the windowed bytes AND the r_cap tables: without this, the
        expensive outlier flushes — exactly what ROADMAP item 4's
        meter must attribute — would be booked with the cheap windowed
        model.  The batch already ingested incrementally, so the
        throughput term carries k=0."""
        lat = self.last_flush_bytes or {}
        thr = flush_ops.throughput_bytes_estimate(self.cfg, 0)
        self.last_flush_bytes = {
            k: lat.get(k, 0) + thr[k] for k in thr
        }

    def _head_round_min_host(self) -> int:
        """Host mirror of ops.state.head_round_min_math (same chain
        and staleness semantics), consulted only on the rare window-
        undershoot path: the round below which the finality gate can
        still decide.  INT32_MAX when every minted chain is stale."""
        base = self.dag.slot_base
        rnd = self._arr("round")
        out = None
        for chain in self.dag.chains:
            if len(chain) == 0 or not chain.window:
                hr = -1   # never minted, or tail evicted (device ce
                          # column 0 is -1 → sentinel round): both stale
                          # once the fleet is >HORIZON rounds ahead
            else:
                hr = int(rnd[chain[-1] - base])
            if hr + HEAD_GATE_HORIZON < self._max_round_cache:
                continue
            out = hr if out is None else min(out, hr)
        return int(INT32_MAX) if out is None else out

    # ------------------------------------------------------------------
    # membership plane (ISSUE 9): validator join/leave as a consensus op

    def _maybe_schedule_membership(self, ev: Event) -> None:
        """Scan one just-committed event for valid membership transition
        txs.  The first valid one with no transition in flight becomes
        the pending transition at boundary rr + EPOCH_LAG; later valid
        ones QUEUE behind it (pipelined membership, ROADMAP 5a) and
        apply FIFO at successive boundaries — a fleet onboarding 50
        validators no longer resubmits 49 times.  Runs on the commit
        path, so every check is deterministic: the same tx is queued
        (or rejected) identically everywhere."""
        from ..membership.transition import (
            MEMBERSHIP_MAGIC, parse_membership_tx,
        )

        for tx in ev.transactions:
            if not tx.startswith(MEMBERSHIP_MAGIC):
                continue
            spec = parse_membership_tx(tx)
            err = self._validate_membership(spec)
            if err is not None:
                self.membership_rejects += 1
                continue
            entry = {
                "kind": spec.kind,
                "pub": spec.pub_hex,
                "addr": spec.net_addr,
                "boundary": ev.round_received + EPOCH_LAG,
                "position": len(self.consensus),
                "tx": bytes(tx),
            }
            if self.pending_membership is None:
                self.pending_membership = entry
            else:
                self.membership_queue.append(entry)

    def _in_flight_membership(self) -> List[dict]:
        head = [self.pending_membership] if self.pending_membership else []
        return head + list(self.membership_queue)

    def _validate_membership(self, spec) -> Optional[str]:
        """Deterministic admissibility of a parsed transition against
        the PROJECTED epoch state — the current peer set with every
        in-flight (pending + queued) transition applied — because that
        is the state the transition will actually apply in.  The epoch
        stamp may name any epoch from the current one through the
        projected apply epoch (pipelined membership): a batch of joins
        all stamped with the submission-time epoch pipelines cleanly,
        while a STALE stamp (below the current epoch — e.g. a replayed
        old leave after the subject rejoined) is still rejected on
        every replica identically."""
        if spec is None:
            return "unparseable transition"
        queue = self._in_flight_membership()
        if len(queue) >= MEMBERSHIP_QUEUE_MAX:
            return "transition queue full"
        apply_epoch = self.epoch + len(queue)
        if not (self.epoch <= spec.epoch <= apply_epoch):
            return (
                f"transition stamped epoch {spec.epoch}, valid range "
                f"[{self.epoch}, {apply_epoch}]"
            )
        # projected membership: current sets plus the in-flight queue
        known = set(self.participants)
        active = {
            pub for pub, cid in self.participants.items()
            if cid not in self.cfg.retired
        }
        for q in queue:
            if q["kind"] == "join":
                known.add(q["pub"])
                active.add(q["pub"])
            else:
                active.discard(q["pub"])
        if spec.kind == "join":
            if spec.pub_hex in known:
                return "join for an existing or queued participant"
        else:
            if spec.pub_hex not in known:
                return "leave for an unknown participant"
            if spec.pub_hex not in active:
                return "leave for a retired or already-leaving participant"
            if len(active) - 1 < 2:
                return "leave would drop the fleet below 2 members"
        if not spec.verify():
            return "bad subject signature"
        return None

    def _maybe_apply_membership(self) -> None:
        if self.pending_membership is None:
            return
        if int(self.state.lcr) >= self.pending_membership["boundary"]:
            self.apply_epoch_transition()

    def apply_epoch_transition(self) -> None:
        """Re-shape the engine at the epoch boundary: every event
        received in rounds <= B is committed (apply requires lcr >= B),
        so the device state splits cleanly — decided history below B is
        frozen under the outgoing peer set, everything above is reset
        and re-decided under the incoming one.

        Join grows the participant axis by one appended column (ids of
        survivors are stable); leave retires the column in the config
        (removing it would renumber every creator).  Either way the
        DagConfig changes, so the compiled-program universe re-keys —
        the AOT manifest records the new epoch's shapes like any other
        config, which is what keeps a churned fleet's restarts warm."""
        from ..ops.epoch import epoch_transition_arrays

        spec = self.pending_membership
        boundary = spec["boundary"]
        old_cfg = self.cfg

        # suspects must be read BEFORE the reset wipes their rounds
        base = self.dag.slot_base
        ne = self.dag.n_events - base
        rnd = self._arr("round")
        suspects = np.nonzero(rnd[:ne] > boundary)[0].astype(np.int32)

        if spec["kind"] == "join":
            cid = self.dag.add_participant(spec["pub"])
            new_cfg = old_cfg._replace(n=old_cfg.n + 1)
        else:
            cid = self.participants[spec["pub"]]
            new_cfg = old_cfg._replace(
                retired=old_cfg.retired + (cid,)
            )

        arrays = epoch_transition_arrays(
            old_cfg, new_cfg, self.state, boundary
        )
        self.cfg = new_cfg
        # jnp.array, NOT jnp.asarray: the transition passes untouched
        # fields through as ZERO-COPY numpy views of the old device
        # buffers, and jnp.asarray would alias them right back into the
        # new state — which the next live_flush DONATES, freeing memory
        # the old arrays still own (on CPU, where donation is real as
        # of jax 0.4.x, this corrupted the heap: live churn found
        # creator columns full of garbage followed by glibc aborts —
        # the deterministic runner was shielded only because its busy
        # fleets always trigger the rescan below, whose XLA outputs
        # launder the aliasing).  An epoch transition is rare; the copy
        # is noise.
        self.state = DagState(
            **{k: jnp.array(v) for k, v in arrays.items()}
        )
        self._view = {}
        self._aot = {}   # executables were compiled for the old config
        if len(suspects):
            self.state = ingest_ops.rescan_rounds(
                self.cfg, self.state, jnp.asarray(self._level_sched(suspects))
            )
            self._view = {}
        self._max_round_cache = int(self.state.max_round)
        self._lcr_cache = int(self.state.lcr)
        # the reset wiped rr above the boundary: held events are
        # undecided again, so the frontier mirror must drop back to the
        # conservative floor (it re-tightens at the next commit pass)
        self._frontier_cache = 0
        self.epoch += 1
        self.membership_log.append({
            "epoch": self.epoch,
            "kind": spec["kind"],
            "pub": spec["pub"],
            "addr": spec["addr"],
            "boundary": boundary,
            "position": spec["position"],
            "cid": cid,
            "tx": spec["tx"],
        })
        self._truncate_membership_log()
        self.pending_membership = None
        if self.membership_queue:
            # pipelined membership: promote the next queued transition.
            # Its boundary must clear the one just applied (held commits
            # above it re-decide under THIS epoch first); the provisional
            # rr + EPOCH_LAG stands when it already does.
            nxt = dict(self.membership_queue.pop(0))
            nxt["boundary"] = max(nxt["boundary"], boundary + 1)
            self.pending_membership = nxt

    def _truncate_membership_log(self) -> None:
        """Bound membership_log growth (ROADMAP 5a): fold entries past
        the retention window into (membership_base_epoch,
        membership_addrs).  The signed chain of custody then starts at
        the base — a verifier whose trusted set predates it must
        bootstrap from a fresher base (membership/epoch.py rejects the
        bridge explicitly), exactly the rolling-window contract the
        event history already has."""
        keep = self.membership_log_keep
        if not keep or len(self.membership_log) <= keep:
            return
        cut = self.membership_log[:-keep]
        for e in cut:
            if e["kind"] == "join":
                # a truncated join's gossip address must survive: the
                # embedded signed tx is gone, and nodes restoring from
                # this engine's checkpoints still need to dial the
                # member (node._sync_membership reconciles from here)
                self.membership_addrs[e["pub"]] = e["addr"]
        self.membership_base_epoch = cut[-1]["epoch"]
        self.membership_log = self.membership_log[-keep:]

    def _level_sched(self, sus: np.ndarray) -> np.ndarray:
        """Level-grouped rescan schedule for local slots ``sus`` (the
        shape rescan_rounds consumes; shared by round repair and epoch
        transitions)."""
        base = self.dag.slot_base
        lev = np.array(
            [self.dag.levels[base + int(s)] for s in sus], np.int64
        )
        order = np.argsort(lev, kind="stable")
        ulev, starts = np.unique(lev[order], return_index=True)
        bounds = list(starts) + [len(sus)]
        t = len(ulev)
        b = max(int(np.max(np.diff(bounds))), 1)
        tpad, bpad = _bucket(t, 1), _bucket(b, 1)
        slot_sched = np.full((tpad, bpad), -1, np.int32)
        for row in range(t):
            grp = sus[order[bounds[row]: bounds[row + 1]]]
            slot_sched[row, : len(grp)] = grp
        return slot_sched

    # ------------------------------------------------------------------
    # rolling-window compaction (reference caches.go:45-76 applied to the
    # dense device state; see ops/state.py compact_impl)

    def maybe_compact(self, force: bool = False) -> int:
        """Evict the longest committed prefix that nothing can reference
        again, and roll the round window up to ``lcr - round_margin``.

        A slot is evictable when (a) it is ordered/committed, (b) its round
        is below the new round-window base (so no witness-table row can
        point at it), and (c) it sits ``seq_window`` seqs behind its
        creator's head (so no incoming event can name it as a parent —
        beyond that, syncs get TooLateError, the reference's rolling-cache
        contract).  Chain slots ascend with seq, so the per-creator seq
        windows and the slot prefix stay consistent by construction.

        Per-creator eviction (ISSUE 8): the per-creator retention in (c)
        is what a SILENT peer weaponizes — its chain head never advances,
        its retained tail sits early in the slot order, and the
        contiguous prefix can never move past it, so one dead peer pins
        eviction (and therefore memory AND fast-forward recovery)
        fleet-wide for the whole outage.  A creator whose head round has
        fallen more than ``inactive_rounds`` decided rounds behind lcr
        is *inactive*: its retention is dropped, its tail evicts with
        the prefix, and ``dag.evicted_heads`` records its (index, hex)
        eviction horizon — the anchor its return bootstraps against
        (verified fast-forward + the continuation insert rule).

        Returns the number of evicted slots.  No-ops while host events are
        pending (their parents must stay resolvable until flushed) and
        while a membership transition is pending (held commits — rr
        decided above the boundary but deliberately not received — must
        not be mistaken for evictable prefix)."""
        if self.dag.pending or self.pending_membership is not None:
            return 0
        lcr = int(self.state.lcr)
        new_r_off = lcr - self.round_margin
        if new_r_off <= 0:
            return 0
        base = self.dag.slot_base
        ne = self.dag.n_events - base
        dr = max(0, new_r_off - self._r_off)

        rr = self._arr("rr")[:ne]
        rnd = self._arr("round")[:ne]
        seq = self._arr("seq")[:ne]
        creator = self._arr("creator")[:ne]
        counts = np.fromiter(
            (len(c) for c in self.dag.chains), np.int64, self.n
        )
        past_window = seq < counts[creator] - self.seq_window
        if self.inactive_rounds is not None:
            inactive = np.zeros(self.n + 1, bool)
            for c, chain in enumerate(self.dag.chains):
                if not chain.window:
                    continue
                head_round = int(rnd[chain[-1] - base])
                inactive[c] = head_round < lcr - self.inactive_rounds
            past_window = past_window | inactive[creator]
        ok = (rr >= 0) & (rnd < new_r_off) & past_window
        k = int(np.argmin(ok)) if not ok.all() else ne
        if (k < self.compact_min and not force) or (k == 0 and dr == 0):
            return 0

        # host first: chain starts after eviction define the seq windows
        self.dag.evict_prefix(base + k)
        new_s_off = np.zeros(self.n + 1, np.int32)
        new_s_off[: self.n] = [c.start for c in self.dag.chains]
        self.state = compact_op(
            self.cfg, self.state,
            jnp.asarray(k, jnp.int32), jnp.asarray(new_s_off),
            jnp.asarray(dr, jnp.int32),
        )
        self._received = {g for g in self._received if g >= base + k}
        self._r_off += dr
        # the evicted prefix is all received, so the frontier shifts
        # with the slots (never below row 0)
        self._frontier_cache = max(self._frontier_cache - k, 0)
        self._view = {}
        self._evicted_creators_cache = sum(
            1 for c in self.dag.chains if len(c) and not c.window
        )
        if self.cfg.ts32:
            # rolling ts32 rebase (PR 7 leftover b): the span guard
            # tracks the LIVE window's timestamp span — the kernel
            # rebases against the live minimum each flush, so eviction
            # moving the frontier narrows the span a wall-clock fleet
            # accumulates (~2 s of ns ticks otherwise trips the guard)
            ne2 = self.dag.n_events - self.dag.slot_base
            ts = self._arr("ts")[:ne2]
            live = self._arr("seq")[:ne2] >= 0
            if live.any():
                self._ts_lo = int(ts[live].min())
                self._ts_hi = int(ts[live].max())
            else:
                self._ts_lo = self._ts_hi = None
        if self.consensus_window is not None:
            self.consensus.evict_to(
                max(self.consensus.start,
                    len(self.consensus) - self.consensus_window)
            )
            # keep the digest anchored at the trimmed window's start so
            # fast-forward snapshots of this window stay re-foldable
            self._digest.evict_to(self.consensus.start)
        return k

    def _round_prn(self, r: int) -> int:
        """Whitening seed: XOR of the round's famous-witness hashes
        (reference roundInfo.go:109-118)."""
        r_loc = r - self._r_off
        if r_loc < 0 or r_loc >= self.cfg.r_cap:
            return 0
        wslot = self._arr("wslot")
        famous = self._arr("famous")
        base = self.dag.slot_base
        res = 0
        for j in range(self.n):
            if wslot[r_loc, j] >= 0 and famous[r_loc, j] == FAME_TRUE:
                res ^= int(
                    self.dag.events[base + int(wslot[r_loc, j])].hex(), 16
                )
        return res

    # ------------------------------------------------------------------
    # wire conversion passthrough

    def to_wire(self, event: Event) -> WireEvent:
        return self.dag.to_wire(event)

    def read_wire_info(self, wevent: WireEvent, overlay=None) -> Event:
        return self.dag.read_wire_info(wevent, overlay)

    # ------------------------------------------------------------------
    # predicate surface (host queries against device arrays; test + runtime)

    def _arr(self, name: str) -> np.ndarray:
        if name not in self._view:
            self._view[name] = np.asarray(getattr(self.state, name))
        return self._view[name]

    def _slot(self, x: str) -> int:
        """Device-local row of event hex x (KeyError if unknown/evicted)."""
        s = self.dag.slot_of.get(x, -1)
        if s < 0:
            raise KeyError(x)
        return s - self.dag.slot_base

    def _event_at(self, local_slot: int) -> Event:
        return self.dag.events[self.dag.slot_base + local_slot]

    def ancestor(self, x: str, y: str) -> bool:
        if x == "" or y == "":
            return False
        if x == y:
            return True
        self.flush()
        try:
            sx, sy = self._slot(x), self._slot(y)
        except KeyError:
            return False
        la = self._arr("la")
        ey = self._event_at(sy)
        cy = self.participants[ey.creator]
        return bool(la[sx, cy] >= ey.index)

    def see(self, x: str, y: str) -> bool:
        return self.ancestor(x, y)

    def self_ancestor(self, x: str, y: str) -> bool:
        if x == "" or y == "":
            return False
        if x == y:
            return True
        try:
            ex = self._event_at(self._slot(x))
            ey = self._event_at(self._slot(y))
        except KeyError:
            return False
        return ex.creator == ey.creator and ex.index >= ey.index

    def strongly_see(self, x: str, y: str) -> bool:
        self.flush()
        try:
            sx, sy = self._slot(x), self._slot(y)
        except KeyError:
            return False
        la, fd = self._arr("la"), self._arr("fd")
        return int(np.count_nonzero(la[sx] >= fd[sy])) >= self.super_majority()

    def oldest_self_ancestor_to_see(self, x: str, y: str) -> str:
        self.flush()
        try:
            sx, sy = self._slot(x), self._slot(y)
        except KeyError:
            return ""
        fd = self._arr("fd")
        ex = self._event_at(sx)
        j = self.participants[ex.creator]
        f = int(fd[sy, j])
        if f <= ex.index and f < int(self.cfg.fd_inf):
            return self.dag.events[self.dag.chains[j][f]].hex()
        return ""

    def round(self, x: str) -> int:
        self.flush()
        return int(self._arr("round")[self._slot(x)])

    def witness(self, x: str) -> bool:
        self.flush()
        return bool(self._arr("witness")[self._slot(x)])

    def round_witnesses(self, r: int) -> List[str]:
        self.flush()
        wslot = self._arr("wslot")
        r_loc = r - self._r_off
        if r_loc < 0 or r_loc >= self.cfg.r_cap:
            return []
        return [
            self._event_at(int(s)).hex() for s in wslot[r_loc] if s >= 0
        ]

    def famous_of(self, r: int, x: str) -> Optional[bool]:
        """Fame trilean of witness x in round r (None = undecided)."""
        self.flush()
        r_loc = r - self._r_off
        if r_loc < 0 or r_loc >= self.cfg.r_cap:
            return None
        wslot = self._arr("wslot")
        famous = self._arr("famous")
        sx = self._slot(x)
        for j in range(self.n):
            if wslot[r_loc, j] == sx:
                f = famous[r_loc, j]
                return None if f == FAME_UNDEFINED else bool(f == FAME_TRUE)
        return None

    def rounds(self) -> int:
        self.flush()
        return int(self.state.max_round) + 1

    # ------------------------------------------------------------------

    def known(self) -> Dict[int, int]:
        return self.dag.known()

    def consensus_events(self) -> List[str]:
        return list(self.consensus)

    def consensus_events_count(self) -> int:
        return len(self.consensus)
