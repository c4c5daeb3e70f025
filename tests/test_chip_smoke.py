"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases' comparisons hold at small shapes on the CPU (the chip run is the
builder's, through the chip tool)."""

import asyncio
import json
import os
import subprocess
import sys

import jax
import numpy as np

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_batch_phase_matches_reference_and_counts_mismatches(capsys):
    facts = chip_smoke.batch_phase(16, 1500, 64, "fast", jax.devices()[0])
    assert facts["ordered"] == facts["reference_ordered"] > 0
    assert not any(facts["mismatches"].values())
    assert json.loads(capsys.readouterr().out.split(": ", 1)[1])["phase"] \
        == "batch 16x1500 fast"

    from babble_tpu.cli import sim_step
    from babble_tpu.native import baseline_consensus
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays

    dag = random_gossip_arrays(8, 500, seed=3)
    cfg, step = sim_step(dag, 64)
    out = step(init_state(cfg), batch_from_arrays(dag))
    _, ref = baseline_consensus(dag)
    slot = int(np.nonzero(ref["rr"] >= 0)[0][0])
    ref["rr"][slot] += 1
    ref["cts"][slot] += 1
    bad = chip_smoke.reference_mismatches(ref, out, 500)
    assert bad == {"round": 0, "witness": 0, "fame": 0, "rr": 1, "cts": 1}


def test_served_phase_small_fleet(tmp_path):
    facts = asyncio.run(chip_smoke.served_phase(
        str(tmp_path), jax.devices()[0], n_txs=40))
    assert facts["ordered_events"] > 0
    assert facts["engine_device"].startswith("cpu")


def test_stream_phase_sharded_matches_one_device(monkeypatch):
    """The --chips 4 comparison on 4 of the 8 virtual CPU devices."""
    monkeypatch.setattr(chip_smoke, "SEED", 9)
    facts = chip_smoke.stream_phase(
        jax.devices()[:4], n=16, events=900, window=560, batch_events=150,
        s_cap=96, r_cap=16, seq_window=12, compact_min=32)
    assert facts["ordered"] > 0 and facts["evicted"] > 0
    assert facts["la_devices"] == [0, 1, 2, 3]
