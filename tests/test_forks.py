"""Fork-aware (byzantine) consensus: differential tests.

Three-way anchor chain:
- ForkOracle (definition-first, hashgraph paper) vs the honest oracle on
  fork-free DAGs — proves the fork-aware semantics degrade to reference
  behavior when nobody equivocates;
- dense branch kernels (ops/forks.py via ForkHashgraph) vs ForkOracle on
  forked DAGs — the byzantine-mode correctness argument;
- fork bookkeeping unit checks (budget, detection, seeing).

The reference has no counterpart to any of this: it rejects forks at
insert (hashgraph.go:366-396) and skips fork detection in See
(hashgraph.go:149-154).
"""

from types import SimpleNamespace

import pytest

from babble_tpu.consensus.byzantine import ForkOracle
from babble_tpu.consensus.fork_engine import ForkHashgraph
from babble_tpu.consensus.oracle import OracleHashgraph
from babble_tpu.ops.forks import ForkBudgetError
from babble_tpu.sim import random_byzantine_dag, random_gossip_dag
from babble_tpu.store.inmem import InmemStore


def _fill(dag, *engines):
    for ev in dag.events:
        for e in engines:
            e.insert_event(ev.clone())


def _assert_match(dag, fo: ForkOracle, fh: ForkHashgraph):
    for ev in dag.events:
        x = ev.hex()
        assert fh.round(x) == fo.round(x), f"round {x[:10]}"
        assert fh.witness(x) == fo.witness(x), f"witness {x[:10]}"
    # fame parity on every witness of every round
    for r in range(fo.max_round() + 1):
        for w in fo.round_witnesses(r):
            assert fh.famous_of(r, w) == fo.famous[w], f"fame r={r} {w[:10]}"
    assert fh.consensus_events() == fo.consensus_events()
    assert fh.lcr == fo.lcr


# ----------------------------------------------------------------------


@pytest.mark.parametrize("n,e,seed", [(4, 150, 1), (5, 200, 2)])
def test_fork_oracle_degrades_to_reference_on_honest_dags(n, e, seed):
    dag = random_gossip_dag(n, e, seed=seed)
    fo = ForkOracle(dag.participants)
    store = InmemStore(dag.participants, cache_size=100_000)
    oh = OracleHashgraph(
        participants=dag.participants, store=store, verify_signatures=False
    )
    _fill(dag, fo, oh)
    fo.run_consensus()
    oh.divide_rounds()
    oh.decide_fame()
    oh.find_order()
    assert fo.consensus_events() == oh.consensus_events()
    for ev in dag.events:
        assert fo.round(ev.hex()) == oh.round(ev.hex())
        assert fo.witness(ev.hex()) == oh.witness(ev.hex())


@pytest.mark.parametrize("k", [1, 2])
def test_dense_matches_oracle_on_honest_dag(k):
    dag = random_gossip_dag(4, 120, seed=7)
    fo = ForkOracle(dag.participants)
    fh = ForkHashgraph(dag.participants, k=k)
    _fill(dag, fo, fh)
    fo.run_consensus()
    fh.run_consensus()
    _assert_match(dag, fo, fh)


@pytest.mark.parametrize(
    "n,e,rate,seed",
    [(6, 200, 0.08, 3), (7, 260, 0.05, 4), (9, 300, 0.1, 5)],
)
def test_dense_matches_oracle_on_byzantine_dag(n, e, rate, seed):
    dag = random_byzantine_dag(n, e, seed=seed, fork_rate=rate)
    fo = ForkOracle(dag.participants)
    fh = ForkHashgraph(dag.participants, k=2)
    _fill(dag, fo, fh)
    fo.run_consensus()
    fh.run_consensus()
    pairs = sum(len(v) for v in fo._fork_pairs.values())
    assert pairs > 0, "generator produced no forks"
    _assert_match(dag, fo, fh)


def test_forked_events_are_unseeable_once_detected():
    """A detector of creator c's fork sees none of c's events (paper
    semantics) — checked on both oracle and dense engine."""
    dag = random_byzantine_dag(6, 200, seed=3, fork_rate=0.08)
    fo = ForkOracle(dag.participants)
    fh = ForkHashgraph(dag.participants, k=2)
    _fill(dag, fo, fh)
    fo.run_consensus()
    fh.run_consensus()
    checked = 0
    for cid, pairs in fo._fork_pairs.items():
        if not pairs:
            continue
        for x in dag.events[-20:]:
            hx = x.hex()
            det_o = fo.detects_fork(hx, cid)
            assert fh.detects_fork(hx, cid) == det_o
            if not det_o:
                continue
            for y in dag.events:
                if fo.participants[y.creator] == cid:
                    assert not fo.see(hx, y.hex())
                    assert not fh.see(hx, y.hex())
                    checked += 1
    assert checked > 0, "no detection case exercised"


def test_fork_budget_rejects_spam():
    """Beyond K-1 forks, the branch budget cuts the equivocator off (the
    dense engine's DoS guard; a real deployment would blacklist)."""
    dag = random_byzantine_dag(
        6, 300, seed=11, fork_rate=0.5, forks_per_node=5
    )
    fh = ForkHashgraph(dag.participants, k=2)
    with pytest.raises(ForkBudgetError):
        for ev in dag.events:
            fh.insert_event(ev.clone())
    # a budget matching the stream accepts it fine
    fh6 = ForkHashgraph(dag.participants, k=6)
    for ev in dag.events:
        fh6.insert_event(ev.clone())
    fh6.run_consensus()
    assert len(fh6.consensus_events()) > 0


def test_fd_reverse_matches_chain_counts():
    """Both fork fd strategies (reverse level scan vs chain-view compare-
    count) must produce identical tensors."""
    import jax
    import numpy as np

    from babble_tpu.ops import forks as F

    dag = random_byzantine_dag(7, 300, seed=9, fork_rate=0.08)
    fh = ForkHashgraph(dag.participants, k=2)
    for ev in dag.events:
        fh.insert_event(ev.clone())
    cfg, _ = fh._run()
    batch = fh.dag.build_batch(cfg)
    la = jax.jit(lambda b: F._la_scan(cfg, b))(batch)
    a = np.asarray(jax.jit(lambda b: F._fd_reverse(cfg, b))(batch))
    c = np.asarray(jax.jit(lambda b: F._fd_chains(cfg, b, la))(batch))
    assert (a == c).all(), f"{int((a != c).sum())} fd mismatches"


def _fresh_rounds(cfg, batch):
    """The inputs of the rounds pass for ``batch`` with its round and
    witness seeds cleared, so the pass decides every event itself."""
    import jax
    import jax.numpy as jnp

    from babble_tpu.ops import forks as F

    batch = batch._replace(rseed=jnp.full_like(batch.rseed, -1),
                           wseed=jnp.full_like(batch.wseed, -1))

    def inputs(b):
        la = F._la_scan(cfg, b)
        det = F._detect(cfg, b, la)
        helper = F._helper(cfg, b, F._fd_reverse(cfg, b),
                           F._first_det(cfg, b, det))
        return la, det, helper

    return (batch,) + jax.jit(inputs)(batch)


def _closure_vs_scan(cfg, batch, band):
    """_rounds_scan's and _rounds_closure's (round, witness, wslot,
    max_round) on a fresh ``batch``, and the closure's band fallbacks."""
    import functools

    import jax

    from babble_tpu.ops import forks as F

    args = _fresh_rounds(cfg, batch)
    scan = jax.jit(functools.partial(F._rounds_scan, cfg))(*args)
    kw = {} if band is None else {"band": band}
    clos = jax.jit(functools.partial(F._rounds_closure, cfg, **kw))(*args)
    return scan, clos[:4], int(clos[5])


def _assert_same_rounds(scan, clos):
    import numpy as np

    for name, a, b in zip(("round", "witness", "wslot", "max_round"),
                          scan, clos):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name
        )


@pytest.mark.parametrize("band", [None, 2], ids=["default_band", "band2"])
@pytest.mark.parametrize("seed,tight", [(3, False), (9, True), (21, True)])
def test_rounds_closure_matches_level_scan(seed, tight, band):
    """_rounds_closure (the per-round closure iteration that replaced the
    level scan for speed) must agree with _rounds_scan bit-for-bit —
    including at TIGHT r_cap = max_round + 1, the capacity where an
    off-by-one in the closure's loop bound silently dropped the top
    round (caught in review; this test is the regression anchor).  At
    the default band width every round stays in its band; a band of 2
    positions runs out and the full pass decides the round, exactly."""
    dag = random_byzantine_dag(9, 400, seed=seed, fork_rate=0.06)
    fh = ForkHashgraph(dag.participants, k=2)
    for ev in dag.events:
        fh.insert_event(ev.clone())
    cfg, _ = fh._run()
    batch = fh.dag.build_batch(cfg)

    scan, clos, fallbacks = _closure_vs_scan(cfg, batch, band)
    if tight:
        cfg = cfg._replace(r_cap=int(scan[3]) + 1)
        scan, clos, fallbacks = _closure_vs_scan(
            cfg, fh.dag.build_batch(cfg), band)
    _assert_same_rounds(scan, clos)
    assert int(scan[3]) >= 1
    if band is None:
        assert fallbacks == 0
    else:
        assert fallbacks > 0


def test_rounds_closure_falls_back_on_a_stalled_chain():
    """Two of four validators gossip only with each other for a while:
    no event of theirs can strongly see a supermajority, so each mints
    more events in one round than the band holds.  The band runs out,
    the full pass decides those rounds, and rounds, witnesses, fame and
    order stay those of the level scan and of the oracle."""
    import numpy as np

    from babble_tpu.core.event import new_event
    from babble_tpu.ops.forks import ROUND_BAND

    n, forker = 4, 3
    rng = np.random.default_rng(17)

    def fake_pub(i):
        return b"\x04" + i.to_bytes(32, "big") + bytes(32)

    participants = {("0x" + fake_pub(i).hex().upper()): i for i in range(n)}
    pubs = [fake_pub(i) for i in range(n)]
    own = [[] for _ in range(n)]          # (hex, index) of each own event
    events = []

    def mint(recv, send, self_parent=None):
        sp = own[recv][-1] if self_parent is None else self_parent
        ts = 1_700_000_000_000_000_000 + len(events) * 2_000_000
        ev = new_event([], (sp[0], own[send][-1][0]), pubs[recv],
                       sp[1] + 1, timestamp=ts)
        ev.r = int(rng.integers(1, 1 << 62))
        ev.s = int(rng.integers(1, 1 << 62))
        events.append(ev)
        own[recv].append((ev.hex(), sp[1] + 1))

    def gossip(steps):
        for _ in range(steps):
            recv = int(rng.integers(0, n))
            send = int(rng.integers(0, n - 1))
            mint(recv, send + (send >= recv))

    for i in range(n):
        ev = new_event([], ("", ""), pubs[i], 0,
                       timestamp=1_700_000_000_000_000_000)
        ev.r, ev.s = i + 1, i + 1
        events.append(ev)
        own[i].append((ev.hex(), 0))
    gossip(60)
    mint(forker, 0, self_parent=own[forker][-2])     # the equivocation
    gossip(40)
    for _ in range(ROUND_BAND + 16):                  # the stalled pair
        mint(0, 1)
        mint(1, 0)
    gossip(120)

    fo = ForkOracle(participants)
    fh = ForkHashgraph(participants, k=2)
    for ev in events:
        fo.insert_event(ev.clone())
        fh.insert_event(ev.clone())
    fo.run_consensus()
    fh.run_consensus()
    per_round = {}
    for ev in events:
        key = (participants[ev.creator], fo.round(ev.hex()))
        per_round[key] = per_round.get(key, 0) + 1
    assert max(per_round.values()) > ROUND_BAND, "no chain stalled"
    assert sum(len(v) for v in fo._fork_pairs.values()) > 0
    _assert_match(SimpleNamespace(events=events), fo, fh)

    cfg, out = fh._run()
    assert int(out.band_fallbacks) > 0
    scan, clos, fallbacks = _closure_vs_scan(
        cfg, fh.dag.build_batch(cfg), None)
    _assert_same_rounds(scan, clos)
    assert fallbacks > 0


def test_windowed_fork_engine_matches_unevicted():
    """Rolling-window byzantine engine (VERDICT r3 weak #4): streaming
    a byzantine DAG through an auto-compacting ForkHashgraph must
    produce the identical committed order, rounds and receive rounds as
    the unevicted engine — seeds pin retained rounds/witness across
    evictions, chain-index values stay absolute, and the fd-safety
    bound keeps median inputs resolvable."""
    dag = random_byzantine_dag(6, 600, seed=11, fork_rate=0.05)
    plain = ForkHashgraph(dag.participants, k=2)
    rolled = ForkHashgraph(dag.participants, k=2, auto_compact=True,
                           round_margin=1, seq_window=6, compact_min=16)

    chunks = 6
    step = (len(dag.events) + chunks - 1) // chunks
    committed_plain = []
    committed_rolled = []
    for i in range(chunks):
        for ev in dag.events[i * step:(i + 1) * step]:
            plain.insert_event(ev)
            # separate Event objects for the rolled engine: the two
            # engines stamp round_received on commit
            w = rolled.read_wire_info(plain.to_wire(ev))
            rolled.insert_event(w)
        committed_plain += [
            (e.hex(), e.round_received, e.consensus_timestamp)
            for e in plain.run_consensus()
        ]
        committed_rolled += [
            (e.hex(), e.round_received, e.consensus_timestamp)
            for e in rolled.run_consensus()
        ]

    assert rolled.dag.evicted > 0, "window never rolled"
    assert committed_rolled == committed_plain
    assert rolled._lcr_cache == plain._lcr_cache
    assert rolled.max_round() == plain.max_round()
    # rounds of still-live events agree (absolute numbering)
    for s in range(len(rolled.dag.events)):
        x = rolled.dag.events[s].hex()
        assert rolled.round(x) == plain.round(x), x
    # the gossip clock stays absolute across eviction
    assert rolled.known() == plain.known()


@pytest.mark.parametrize("seed", [11, 9])
def test_windowed_rounds_closure_band_matches_full_pass(seed, monkeypatch):
    """The rolling window hands the rounds pass seeded rounds: retained
    events above a round's frontier (``rseed > r``, the parent-round
    lift) and fork common prefixes, one event in two branch views, both
    inside the band.  On every window of a streamed byzantine DAG the
    band's result equals the full pass's (a band of one position falls
    back every round), and the windowed engine still orders as the
    unevicted one."""
    import functools

    import jax
    import numpy as np

    from babble_tpu.consensus import fork_engine
    from babble_tpu.ops import forks as F

    windows = []

    def recording(cfg, batch):
        out = F.fork_pipeline(cfg, batch)
        if (np.asarray(batch.rseed) >= 0).any():
            windows.append((cfg, batch, out))
        return out

    monkeypatch.setattr(fork_engine, "fork_pipeline", recording)
    dag = random_byzantine_dag(6, 600, seed=seed, fork_rate=0.05)
    plain = ForkHashgraph(dag.participants, k=2)
    rolled = ForkHashgraph(dag.participants, k=2, auto_compact=True,
                           round_margin=1, seq_window=6, compact_min=16)
    step = 50
    committed_plain, committed_rolled = [], []
    for i in range(0, len(dag.events), step):
        for ev in dag.events[i:i + step]:
            plain.insert_event(ev)
            rolled.insert_event(rolled.read_wire_info(plain.to_wire(ev)))
        committed_plain += [(e.hex(), e.round_received)
                            for e in plain.run_consensus()]
        committed_rolled += [(e.hex(), e.round_received)
                             for e in rolled.run_consensus()]
    assert rolled.dag.evicted > 0, "window never rolled"
    assert committed_rolled == committed_plain

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def rounds(cfg, batch, out, band):
        helper = F._helper(cfg, batch, out.fd,
                           F._first_det(cfg, batch, out.det))
        return F._rounds_closure(cfg, batch, out.la, out.det, helper,
                                 band=band)

    lifted = shared = 0
    for cfg, batch, out in windows:
        assert int(out.band_fallbacks) == 0
        full = rounds(cfg, batch, out, 1)
        assert int(full[5]) > 0
        _assert_same_rounds(
            (out.round, out.witness, out.wslot, out.max_round), full[:4])
        # an unassigned event whose other parent is seeded above its
        # self-parent's round: the lift decides it inside the band
        rseed, rnd = np.asarray(batch.rseed), np.asarray(out.round)
        sp, op = np.asarray(batch.sp), np.asarray(batch.op)
        new = (rseed < 0) & (sp >= 0) & (op >= 0)
        lifted += int((new & (rseed[op] > rnd[sp])).sum())
        # a fork's common prefix retained in the window
        cp, s_off = np.asarray(batch.cp), np.asarray(batch.s_off)
        off = ~np.eye(cfg.b, dtype=bool) & (cp > 0) & (cp < 2**31 - 1)
        shared += int((off & (cp > s_off[:, None])).sum())
    assert lifted > 0, "no seeded round above a frontier"
    assert shared > 0, "no fork common prefix in a window"


def test_laggard_chains_block_unsafe_eviction():
    """ADVICE r4 medium #1: lcr advances on a supermajority and can
    outrun laggard chains.  Two creators that gossip only with each
    other stay at low rounds while the fast group's lcr climbs; when
    they finally merge back, their events legitimately get LOW rounds,
    and assigning those needs the low-round witnesses of the fast
    creators.  A windowed replica that evicted those witnesses would
    compute different rounds than an unevicted one — consensus
    divergence across differently-windowed replicas.  maybe_compact's
    round-consistency gate (max evicted round < min retained round)
    must keep the two engines bit-identical."""
    import numpy as np

    from babble_tpu.core.event import new_event

    n, n_fast = 9, 7            # 7 >= 2*9//3 + 1: fast supermajority
    rng = np.random.default_rng(5)

    def fake_pub(i):
        return b"\x04" + i.to_bytes(32, "big") + bytes(32)

    participants = {("0x" + fake_pub(i).hex().upper()): i for i in range(n)}
    pubs = [fake_pub(i) for i in range(n)]
    heads, seqs = [None] * n, [0] * n
    events = []
    t = [0]

    def mint(recv, send):
        t[0] += 1
        ts = 1_700_000_000_000_000_000 + t[0] * 2_000_000
        parents = ("", "") if heads[recv] is None else (
            heads[recv], heads[send])
        ev = new_event([], parents, pubs[recv], seqs[recv], timestamp=ts)
        ev.r = int(rng.integers(1, 1 << 62))
        ev.s = int(rng.integers(1, 1 << 62))
        events.append(ev)
        heads[recv] = ev.hex()
        seqs[recv] += 1

    for i in range(n):
        mint(i, i)              # roots
    for step in range(700):
        recv = int(rng.integers(0, n_fast))
        send = int(rng.integers(0, n_fast - 1))
        if send >= recv:
            send += 1
        mint(recv, send)        # fast group gossips among itself
        if step % 60 == 30:
            mint(7, 8)          # laggards whisper to each other only
        if step % 60 == 45:
            mint(8, 7)
    mint(7, 8)                  # the late laggard merge (low round)
    mint(0, 7)                  # fast group finally hears the laggards
    for _ in range(60):
        recv = int(rng.integers(0, n_fast))
        send = int(rng.integers(0, n_fast - 1))
        if send >= recv:
            send += 1
        mint(recv, send)

    plain = ForkHashgraph(participants, k=2)
    rolled = ForkHashgraph(participants, k=2, auto_compact=True,
                           round_margin=1, seq_window=4, compact_min=8)
    committed_plain, committed_rolled = [], []
    chunk = 80
    for i in range(0, len(events), chunk):
        for ev in events[i:i + chunk]:
            plain.insert_event(ev)
            rolled.insert_event(rolled.read_wire_info(plain.to_wire(ev)))
        committed_plain += [
            (e.hex(), e.round_received) for e in plain.run_consensus()
        ]
        committed_rolled += [
            (e.hex(), e.round_received) for e in rolled.run_consensus()
        ]

    assert plain.max_round() >= 4, "fast group never outran the laggards"
    assert committed_rolled == committed_plain
    assert rolled._lcr_cache == plain._lcr_cache
    # every live event's round matches the unevicted engine — including
    # the late merge events whose rounds sit far below lcr
    for s in range(len(rolled.dag.events)):
        x = rolled.dag.events[s].hex()
        assert rolled.round(x) == plain.round(x), x


def test_fork_pipeline_sentinel_rows_stay_sentinel():
    """Regression for the ISSUE-12 ``partition-spec-coverage`` findings:
    the fork kernels restored their sentinel/dump rows with
    static-index ``.at[cap].set()`` writes — which lower to
    dynamic-update-slices whose per-shard start clamps under SPMD
    partitioning and corrupts earlier shards once the pipeline runs
    through make_sharded_fork_step (ops/state.py set_sentinel
    docstring; observed on ce/cnt for the honest pipeline).  The
    rewritten elementwise restores must leave every sentinel row
    exactly sentinel-valued; output parity with the oracle is pinned
    by the differential tests above."""
    import jax
    import numpy as np

    from babble_tpu.ops import forks as F

    dag = random_byzantine_dag(6, 220, seed=4, fork_rate=0.1)
    fh = ForkHashgraph(dag.participants, k=2)
    for ev in dag.events:
        fh.insert_event(ev.clone())
    cfg, _ = fh._run()
    batch = fh.dag.build_batch(cfg)

    la = np.asarray(jax.jit(lambda b: F._la_scan(cfg, b))(batch))
    fd = np.asarray(jax.jit(lambda b: F._fd_reverse(cfg, b))(batch))
    assert (la[cfg.e_cap] == -1).all()
    assert (fd[cfg.e_cap] == np.iinfo(np.int32).max).all()

    out = F.fork_pipeline(cfg, batch)
    assert int(np.asarray(out.round)[cfg.e_cap]) == -1
    assert not bool(np.asarray(out.witness)[cfg.e_cap])
    assert (np.asarray(out.wslot)[cfg.r_cap] == -1).all()


def test_fork_engine_clamps_lying_timestamps():
    """Regression for the PR-16 parity gap: fork ingestion routes
    through the same per-creator effective-timestamp clamp as the
    fused/wide engines (core/dag.py clamp_eff_ts), so a lying-clock
    creator cannot drag the round-received medians more than one clamp
    window forward.  The oracle mirrors the clamp (differential stays
    the ground truth) and the clamped values survive a snapshot
    round-trip."""
    import numpy as np

    from babble_tpu.core.event import new_event
    from babble_tpu.store.checkpoint import load_snapshot, snapshot_bytes

    n, liar = 4, 3
    lie_ns = 3_600_000_000_000      # claims one hour in the future
    rng = np.random.default_rng(11)

    def fake_pub(i):
        return b"\x04" + i.to_bytes(32, "big") + bytes(32)

    participants = {("0x" + fake_pub(i).hex().upper()): i for i in range(n)}
    pubs = [fake_pub(i) for i in range(n)]
    heads, seqs = [None] * n, [0] * n
    events = []
    t = [0]

    def mint(recv, send):
        t[0] += 1
        ts = 1_700_000_000_000_000_000 + t[0] * 2_000_000
        if recv == liar and heads[recv] is not None:
            ts += lie_ns
        parents = ("", "") if heads[recv] is None else (
            heads[recv], heads[send])
        ev = new_event([], parents, pubs[recv], seqs[recv], timestamp=ts)
        ev.r = int(rng.integers(1, 1 << 62))
        ev.s = int(rng.integers(1, 1 << 62))
        events.append(ev)
        heads[recv] = ev.hex()
        seqs[recv] += 1

    for i in range(n):
        mint(i, i)
    for _ in range(140):
        recv = int(rng.integers(0, n))
        send = int(rng.integers(0, n - 1))
        if send >= recv:
            send += 1
        mint(recv, send)

    fo = ForkOracle(participants)
    fh = ForkHashgraph(participants, k=2)
    _fill(type("D", (), {"events": events})(), fo, fh)
    committed_h = fh.run_consensus()
    committed_o = fo.run_consensus()

    dag = fh.dag
    clamped = 0
    for s, ev in enumerate(dag.events):
        eff, claimed = dag.eff_ts[s], ev.body.timestamp
        # the oracle's mirror is bit-identical per event
        assert fo._eff_ts[ev.hex()] == eff, ev.hex()[:10]
        if participants[ev.creator] == liar and ev.body.index > 0:
            # a lie is admitted at most one clamp window ahead of the
            # parents; a persistent liar drifts at W per event, not
            # instantly (early lies MUST be cut down)
            if eff < claimed:
                clamped += 1
        else:
            # honest events only ever get raised (parent monotonicity)
            assert eff >= claimed
    assert clamped > 0, "generator produced no lying events"

    # the committed order AND the consensus timestamps stay differential
    assert [(e.hex(), e.consensus_timestamp) for e in committed_h] == \
        [(e.hex(), e.consensus_timestamp) for e in committed_o]
    assert committed_h, "no events reached consensus"

    # clamp state survives the fast-forward snapshot seam
    fh2 = load_snapshot(snapshot_bytes(fh), verify_events=False)
    assert fh2.dag.eff_ts == dag.eff_ts
