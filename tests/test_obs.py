"""Unit tier for babble_tpu/obs (ISSUE 2): registry semantics, bucket
math, exposition format, span trees, loop-lag probe.

Deliberately cheap: no JAX device work anywhere in this module (the
registry/tracer are stdlib-only by contract), so the tier-1 cost is
milliseconds, besides one short host-only profiler trace (a span on
the profiler's clock).  The live-node integration surface (/metrics on
a real Service) is covered in test_service_debug.py.
"""

import asyncio
import math
import threading

import pytest

from babble_tpu.obs import (
    LATENCY_BUCKETS,
    SIZE_BUCKETS,
    LoopLagProbe,
    Registry,
    SpanTracer,
)

# ----------------------------------------------------------------------
# registry + instruments


def test_counter_monotone():
    r = Registry()
    c = r.counter("txs_total", "t")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec_and_callback():
    r = Registry()
    g = r.gauge("depth", "d")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6
    q = [1, 2, 3]
    fn = r.gauge("qsize", "q")
    fn.set_function(lambda: len(q))
    assert fn.value == 3
    q.append(4)
    assert fn.value == 4


def test_dead_gauge_callback_does_not_break_scrape():
    r = Registry()
    g = r.gauge("boom", "b")
    g.set_function(lambda: 1 / 0)
    assert math.isnan(g.value)
    # and exposition still renders the whole page
    assert "boom NaN" in r.exposition()


def test_histogram_bucket_math_inclusive_upper_bounds():
    """Prometheus `le` is inclusive: a sample exactly on a bound lands
    in that bucket; cumulative counts are monotone to +Inf."""
    r = Registry()
    h = r.histogram("lat", "l", buckets=(0.5, 1.0, 2.0))
    for v in (0.25, 0.5, 1.0, 1.5, 99.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 5
    assert d["last"] == 99.0
    assert d["buckets"] == [
        [0.5, 2],      # 0.25, 0.5 (inclusive)
        [1.0, 3],      # + 1.0 (inclusive)
        [2.0, 4],      # + 1.5
        ["+Inf", 5],   # + 99.0
    ]
    assert d["sum"] == pytest.approx(102.25)


def test_histogram_rejects_bad_buckets():
    r = Registry()
    with pytest.raises(ValueError):
        r.histogram("bad", "b", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        r.histogram("bad2", "b", buckets=())


def test_shared_bucket_shapes_are_increasing():
    for buckets in (LATENCY_BUCKETS, SIZE_BUCKETS):
        assert all(a < b for a, b in zip(buckets, buckets[1:]))


def test_histogram_timer():
    r = Registry()
    h = r.histogram("t", "t")
    with h.time():
        pass
    assert h.count == 1 and h.last >= 0.0


def test_registry_idempotent_and_kind_conflict():
    r = Registry()
    a = r.counter("x_total", "x")
    assert r.counter("x_total", "x") is a
    with pytest.raises(ValueError):
        r.gauge("x_total", "x")
    with pytest.raises(ValueError):
        r.counter("x_total", "x", labelnames=("peer",))
    with pytest.raises(ValueError):
        r.counter("bad name", "nope")
    with pytest.raises(ValueError):
        r.counter("ok_total", "x", labelnames=("bad-label",))
    # histograms: the same name with a DIFFERENT bucket layout is a
    # conflict (a silently ignored layout would collapse one side's
    # distribution into +Inf), but re-asking with the same layout —
    # even spelled with an explicit trailing +Inf — is idempotent
    h = r.histogram("d_seconds", "d", buckets=(0.1, 1.0))
    assert r.histogram("d_seconds", "d", buckets=(0.1, 1.0)) is h
    assert r.histogram(
        "d_seconds", "d", buckets=(0.1, 1.0, float("inf"))) is h
    with pytest.raises(ValueError):
        r.histogram("d_seconds", "d", buckets=(1.0, 4.0, 16.0))


def test_labelled_family_and_solo_guard():
    r = Registry()
    fam = r.counter("rpc_total", "r", labelnames=("verb",))
    fam.labels("sync").inc(3)
    fam.labels("ff").inc()
    assert fam.labels("sync").value == 3
    with pytest.raises(ValueError):
        fam.inc()          # labelled family has no solo child
    with pytest.raises(ValueError):
        fam.labels("a", "b")   # label arity


def test_exposition_golden():
    """The Prometheus text format, pinned byte-for-byte on a small
    registry (binary-exact sample values so repr() is stable)."""
    r = Registry()
    c = r.counter("test_total", "help text")
    c.inc()
    c.inc(2)
    g = r.gauge("queue_depth", "q")
    g.set(5)
    h = r.histogram("lat_seconds", "l", buckets=(0.5, 1.0))
    for v in (0.25, 0.5, 5.0):
        h.observe(v)
    lab = r.counter("rpc_total", "r", labelnames=("verb",))
    lab.labels('we"ird\n').inc()
    assert r.exposition() == (
        '# HELP lat_seconds l\n'
        '# TYPE lat_seconds histogram\n'
        'lat_seconds_bucket{le="0.5"} 2\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        'lat_seconds_sum 5.75\n'
        'lat_seconds_count 3\n'
        '# HELP queue_depth q\n'
        '# TYPE queue_depth gauge\n'
        'queue_depth 5\n'
        '# HELP rpc_total r\n'
        '# TYPE rpc_total counter\n'
        'rpc_total{verb="we\\"ird\\n"} 1\n'
        '# HELP test_total help text\n'
        '# TYPE test_total counter\n'
        'test_total 3\n'
    )
    assert r.series_count() == 8


def test_snapshot_is_json_able():
    import json

    r = Registry()
    r.counter("a_total", "a").inc()
    h = r.histogram("b_seconds", "b", labelnames=("phase",))
    h.labels("x").observe(0.5)
    snap = json.loads(json.dumps(r.snapshot()))
    assert snap["a_total"]["kind"] == "counter"
    assert snap["a_total"]["series"][0]["value"] == 1
    series = snap["b_seconds"]["series"][0]
    assert series["labels"] == {"phase": "x"}
    assert series["count"] == 1 and series["last"] == 0.5


def test_registry_concurrent_updates_are_exact():
    """The worker threads that drive the device pipeline update the
    same instruments as the event loop: increments must never be lost
    (the whole point of the per-child locks)."""
    r = Registry()
    c = r.counter("n_total", "n")
    h = r.histogram("h_seconds", "h")
    fam = r.counter("lab_total", "l", labelnames=("t",))
    n_threads, n_iter = 8, 2000

    def work(i):
        for _ in range(n_iter):
            c.inc()
            h.observe(0.001)
            fam.labels(str(i % 2)).inc()

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    total = sum(child.value for _, child in fam.children())
    assert total == n_threads * n_iter


# ----------------------------------------------------------------------
# spans


def test_span_nesting_builds_a_tree():
    tr = SpanTracer()
    with tr.span("gossip", peer="127.0.0.1:1337"):
        with tr.span("sync_apply"):
            tr.record("device_step", 0.005, events=12)
    trees = tr.trees()
    assert len(trees) == 1
    root = trees[0]
    assert root["name"] == "gossip"
    assert root["attrs"] == {"peer": "127.0.0.1:1337"}
    (child,) = root["children"]
    assert child["name"] == "sync_apply"
    (leaf,) = child["children"]
    assert leaf["name"] == "device_step"
    assert leaf["dur_s"] == 0.005
    assert root["dur_s"] >= child["dur_s"]


def test_span_ring_is_bounded_and_counts_drops():
    tr = SpanTracer(capacity=4)
    for i in range(7):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.dump()) == 4
    assert tr.dropped == 3
    tr.clear()
    assert tr.dump() == [] and tr.dropped == 0
    # a child whose parent is not in the ring surfaces as a root
    # (partial trees beat silently vanishing ones) — here because the
    # parent span is still open when the ring is dumped
    with tr.span("in_flight"):
        tr.record("orphan", 0.001)
        (root,) = tr.trees()
    assert root["name"] == "orphan"
    assert root["parent"] is not None   # it HAS a parent — just not retained
    assert root["children"] == []


def test_span_error_annotation():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (span,) = tr.dump()
    assert span["error"] == "RuntimeError"


def test_traced_decorator_sync_and_async():
    tr = SpanTracer()

    @tr.traced()
    def sync_fn():
        return 1

    @tr.traced("custom")
    async def async_fn():
        return 2

    assert sync_fn() == 1
    assert asyncio.run(async_fn()) == 2
    names = {s["name"] for s in tr.dump()}
    assert "custom" in names
    assert any("sync_fn" in n for n in names)


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """A span taken under a profiler trace is a host event named
    ``babble_<name>``; one recorded after the fact stays ring-only."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = SpanTracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("gossip"):
            pass
        tr.record("consensus", 0.001)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    host = {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert "babble_gossip" in host
    assert "babble_consensus" not in host
    assert {s["name"] for s in tr.dump()} == {"gossip", "consensus"}


def test_concurrent_tasks_get_separate_parents():
    """Two interleaving asyncio tasks must not adopt each other's spans
    as parents (the contextvars propagation contract)."""
    tr = SpanTracer()

    async def one(name):
        with tr.span(name):
            await asyncio.sleep(0.01)
            tr.record(f"{name}.leaf", 0.001)

    async def go():
        await asyncio.gather(one("a"), one("b"))

    asyncio.run(go())
    trees = {t["name"]: t for t in tr.trees()}
    assert set(trees) == {"a", "b"}
    for name, tree in trees.items():
        assert [c["name"] for c in tree["children"]] == [f"{name}.leaf"]


# ----------------------------------------------------------------------
# loop-lag probe


def test_loop_lag_probe_records_samples():
    async def go():
        reg = Registry()
        probe = LoopLagProbe(reg, interval=0.01)
        t1 = probe.start()
        assert probe.start() is t1   # idempotent while running
        await asyncio.sleep(0.06)
        probe.stop()
        h = reg.get("babble_event_loop_lag_seconds")
        assert h.count >= 2
        assert h.last >= 0.0

    asyncio.run(go())

