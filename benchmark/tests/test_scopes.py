"""Device time by phase (benchmark/scopes.py): the scope map on the
step's compiled text and on hand-written HLO lines for each fallback
rule, the per-scope union on made-up nested intervals, and both on a
small trace recorded on the chip beside its program's text
(tests/data/scopes.*.gz: one step of ``cli.sim_step`` at 4 x 512, r_cap
256, ``random_gossip_arrays`` seed 7, traced with the host tracer off;
the text without its file and stack-frame tables), against numbers
read from it by hand."""

import gzip
import os

import pytest

from benchmark import scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
PATHS = ("babble_ingest/la", "babble_ingest/fd", "babble_ingest/rounds",
         "babble_fame", "babble_order")


def _whiles(smap: dict) -> dict:
    return {n: p for n, p in smap.items() if n.startswith("while")}


@pytest.mark.parametrize("op_name, path", [
    ("jit(f)/babble_ingest/la/while/body/max", "babble_ingest/la"),
    ("jit(f)/babble_ingest/rounds/jit(clip)/min", "babble_ingest/rounds"),
    ("jit(f)/babble_ingest/jit(_where)/select_n", "babble_ingest"),
    ("jit(f)/babble_order/while/body/la/add", "babble_order"),
    ("jit(f)/babble_fame/reduce_sum", "babble_fame"),
    ("reduce_sum", None),
])
def test_scope_of_keeps_the_phase_and_its_child(op_name, path):
    assert scopes.scope_of(op_name) == path


HLO = """\
HloModule jit_step, is_scheduled=true

%fused_la (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/babble_ingest/la/add"}
}

%fused_mix (param_0.1: s32[4]) -> s32[4] {
  %param_0.1 = s32[4]{0} parameter(0)
  %multiply.1 = s32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/babble_ingest/la/mul"}
  ROOT %subtract.1 = s32[4]{0} subtract(%multiply.1, %param_0.1), metadata={op_name="jit(step)/babble_ingest/rounds/while/body/sub"}
}

%body.1 (p: (s32[], s32[4])) -> (s32[], s32[4]) {
  %p = (s32[], s32[4]{0}) parameter(0)
  %get-tuple-element.1 = s32[4]{0} get-tuple-element(%p), index=1
  %copy.1 = s32[4]{0} copy(%get-tuple-element.1)
  %get-tuple-element.2 = s32[] get-tuple-element(%p), index=0
  ROOT %tuple.1 = (s32[], s32[4]{0}) tuple(%get-tuple-element.2, %copy.1)
}

%cond.1 (p.1: (s32[], s32[4])) -> pred[] {
  %p.1 = (s32[], s32[4]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main.1 (x: s32[4]) -> s32[4] {
  %x = s32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = s32[4]{0} fusion(%x), kind=kLoop, calls=%fused_la
  %fusion.2 = s32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_mix
  %constant.2 = s32[] constant(0)
  %tuple.0 = (s32[], s32[4]{0}) tuple(%constant.2, %fusion.2)
  %while.1 = (s32[], s32[4]{0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/babble_fame/while"}
  ROOT %copy.2 = s32[4]{0} copy(%fusion.2)
}
"""


@pytest.mark.parametrize("name, path", [
    ("while.1", "babble_fame"),           # its own op_name
    ("fusion.1", "babble_ingest/la"),     # from its fused computation
    ("fusion.2", "babble_ingest"),        # a la + rounds mix: the prefix
    ("copy.1", "babble_fame"),            # a while body's: the while's
    ("constant.1", "babble_fame"),        # a while condition's, likewise
    ("copy.2", "unscoped"),               # nothing to take it from
    ("x", "unscoped"),                    # an op_name with no scope
])
def test_scope_map_fallback_rules(name, path):
    assert scopes.scope_map(HLO)[name] == path


def test_scope_map_on_the_step_compiled_here():
    """The CPU program of the step (4 x 512) maps instructions to all
    five paths, and the looping phases own their loops (XLA's CPU
    backend drops fd's one-trip compare-count loop at this size)."""
    import jax

    from babble_tpu.cli import sim_step
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays

    dag = random_gossip_arrays(4, 512, seed=7)
    cfg, step = sim_step(dag, 256)
    text = step.lower(jax.eval_shape(lambda: init_state(cfg)),
                      batch_from_arrays(dag)).compile().as_text()
    smap = scopes.scope_map(text)
    assert set(PATHS) <= set(smap.values())
    assert set(PATHS) - {"babble_ingest/fd"} <= set(_whiles(smap).values())


def test_reduce_scopes_counts_a_while_and_its_body_once(monkeypatch):
    devices = {
        "/device:TPU:0": {"ops": [
            (0, 100, "while.1"), (10, 20, "fusion.1"), (30, 50, "fusion.3"),
            (100, 130, "while.2"), (105, 110, "copy.1"), (140, 150, "x.9"),
        ], "modules": [(0, 150, "jit_step(1)")]},
        "/device:TPU:1": {"ops": [(0, 60, "while.1"), (20, 40, "fusion.1")],
                          "modules": [(0, 60, "jit_step(1)")]},
        "/device:TPU:2": {"ops": [], "modules": []},
    }
    smap = {"while.1": "babble_fame", "fusion.1": "babble_fame",
            "fusion.3": "babble_fame", "while.2": "babble_ingest/la",
            "copy.1": "babble_ingest/la"}
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "read", lambda path: (devices, []))
    red = scopes.reduce_scopes("unused", smap)
    assert red["devices"] == 2
    assert red["scopes"] == pytest.approx({
        "babble_fame": (100 + 60) / 2 / 1e9,
        "babble_ingest/la": 30 / 2 / 1e9,
        "unscoped": 10 / 2 / 1e9}, abs=1e-15)
    assert red["ops_busy_s"] == pytest.approx((140 + 60) / 2 / 1e9, abs=1e-15)


@pytest.fixture()
def recorded(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "scopes.xplane.pb.gz"), "rb") as f:
        (d / "scopes.xplane.pb").write_bytes(f.read())
    with gzip.open(os.path.join(DATA, "scopes.hlo.txt.gz"), "rt") as f:
        return str(tmp_path), scopes.scope_map(f.read())


def test_scope_map_on_the_chip_program(recorded):
    _, smap = recorded
    assert set(_whiles(smap).values()) == set(PATHS)


def test_reduce_scopes_matches_the_chip_step_read_by_hand(recorded):
    # read by hand from the file: 10,785 operations on /device:TPU:0,
    # every name in the program's text; per path the union of their
    # intervals in the whole nanoseconds the reader gives, and the union
    # of all, 4,258,049 ns, which the paths add up to
    trace_dir, smap = recorded
    red = scopes.reduce_scopes(trace_dir, smap)
    assert red["devices"] == 1
    assert red["ops_busy_s"] == pytest.approx(0.004258049, rel=1e-12)
    assert red["scopes"] == pytest.approx({
        "babble_fame": 0.000325245,
        "babble_ingest": 0.000112582,
        "babble_ingest/fd": 0.000013518,
        "babble_ingest/la": 0.000838919,
        "babble_ingest/rounds": 0.000743195,
        "babble_order": 0.002216652,
        "unscoped": 0.000007938}, rel=1e-12)
    assert sum(red["scopes"].values()) == pytest.approx(red["ops_busy_s"],
                                                        rel=1e-12)
