"""The trace reduction (benchmark/trace.py): its interval arithmetic on
made-up intervals, and the whole reduction on a small trace recorded
on the chip (tests/data/probe.xplane.pb: four steps of a jitted
function with the babble_ingest / babble_fame / babble_order scopes,
each step inside a ``bench_probe_step`` span and followed by a 50 ms
``bench_probe_sleep`` span), against numbers read from it by hand."""

import os
import shutil

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_gaps_skip_seams_shorter_than_the_floor():
    busy = [(0, 100), (105, 200), (50_000, 60_000)]
    assert trace.gaps(busy, 0, 100_000, min_ns=10) == [
        (200, 50_000), (60_000, 100_000)]


def test_gap_label_is_the_span_covering_most_of_it():
    spans = [(0, 40, "bench_a"), (30, 100, "bench_b")]
    assert trace.label_gap((20, 60), spans) == "bench_b"
    assert trace.label_gap((200, 300), spans) == "no span"


def test_op_name_drops_the_hlo_text():
    assert trace.op_name("%while.165 = (u32[]) while(...)") == "while.165"
    assert trace.op_name("fusion.3") == "fusion.3"


@pytest.fixture()
def probe_dir(tmp_path):
    src = os.path.join(DATA, "probe.xplane.pb")
    if not os.path.exists(src):
        pytest.skip("no recorded chip trace")
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(src, d / "probe.xplane.pb")
    return str(tmp_path)


def test_reduce_matches_the_probe_read_by_hand(probe_dir):
    # read by hand from the file: 60 operations on /device:TPU:0, none
    # overlapping, 6,247,815 ns in all, inside four jit_step modules of
    # 1,560,622 + 1,562,289 + 1,563,436 + 1,561,555 = 6,247,902 ns; the
    # window of 212.346 ms was measured around the loop
    red = trace.reduce(probe_dir, 0.2123461379999938)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.006247902, abs=1e-12)
    assert red["top_ops"][0][0] == "sort.6"
    assert red["top_ops"][0][1] == pytest.approx(0.005467497, abs=1e-12)
    idle = dict(red["top_idle"])
    # the device is idle through the four 50 ms sleeps
    assert idle["bench_probe_sleep"] > 0.19
    assert sum(idle.values()) == pytest.approx(
        0.2123461379999938 - 0.006247902, rel=1e-3)
