"""AOT prewarm for the non-fused engines (ROADMAP 3c leftover).

The fused engine's manifest/prewarm pipeline landed in PR 7
(tests/test_flush.py::test_aot_prewarm_manifest_round_trip); these pin
the KERNEL_SPLIT-gate removal — wide and fork (byzantine) engines stop
paying their first-call compiles mid-gossip:

- fork: a cold run RECORDS its pipeline capacity shape; a prewarmed
  twin pre-sizes to it and pays the whole-pipeline jit at boot, after
  which the same workload triggers ZERO further XLA compiles;
- wide: prewarm runs one warmup pass over the empty state, compiling
  the fixed-shape march/fame/order programs at boot, and is a semantic
  no-op (bit-identical consensus vs an un-prewarmed twin).
"""

import json
import os
import subprocess
import sys

import pytest

from babble_tpu.consensus.fork_engine import ForkHashgraph
from babble_tpu.consensus.wide_engine import WideHashgraph
from babble_tpu.ops import aot
from babble_tpu.sim.generator import random_gossip_dag


def _drive(engine, dag, every=6):
    for i, ev in enumerate(dag.events):
        engine.insert_event(ev.clone())
        if (i + 1) % every == 0:
            engine.run_consensus()
    engine.run_consensus()


def test_fork_prewarm_presizes_and_matches(tmp_path):
    """Cold fork runs RECORD their pipeline shapes (capacity triple +
    bucketed sched dims); a prewarmed twin pre-sizes to the merged caps
    at boot — the demand-driven growth sequence (a full pipeline
    re-jit per step) is gone — replays the sched buckets through the
    real jit entry, and reaches bit-identical consensus."""
    cache = str(tmp_path / "aot")
    dag = random_gossip_dag(5, 70, seed=21)

    f1 = ForkHashgraph(dag.participants, k=3, verify_signatures=False)
    f1._aot_dir = cache
    _drive(f1, dag)
    entries = [e for e in aot.load_manifest(cache)
               if e.get("kind") == "fork"]
    assert entries, "cold fork run must record its pipeline shapes"
    assert all(e["n"] == 5 and e["k"] == 3 for e in entries)
    assert any("sched" in e for e in entries)

    f2 = ForkHashgraph(dag.participants, k=3, verify_signatures=False)
    res = aot.prewarm_engine(f2, cache)
    assert res["from_manifest"] >= 1
    assert res["compiled"] >= 1, "prewarm must replay the sched buckets"
    assert f2._caps == f1._caps, "prewarm must pre-size to recorded caps"
    caps_at_boot = f2._caps
    _drive(f2, dag)
    assert f2._caps == caps_at_boot, "caps must not grow mid-stream"
    assert f2.consensus == f1.consensus


_CHILD = r"""
import json, sys
from babble_tpu.ops import aot
from babble_tpu.consensus.fork_engine import ForkHashgraph
from babble_tpu.sim.generator import random_gossip_dag

cache, warm = sys.argv[1], sys.argv[2] == "warm"
aot.configure(cache)
dag = random_gossip_dag(4, 56, seed=21)
eng = ForkHashgraph(dag.participants, k=2, verify_signatures=False)
eng._aot_dir = cache
if warm:
    aot.prewarm_engine(eng, cache)
print("=== BOOT DONE ===", flush=True)
sys.stderr.write("=== BOOT DONE ===\n")
sys.stderr.flush()
for i, ev in enumerate(dag.events):
    eng.insert_event(ev.clone())
    if (i + 1) % 6 == 0:
        eng.run_consensus()
eng.run_consensus()
print(json.dumps({"consensus": len(eng.consensus),
                  "cache_hits": aot.compile_counts()["cache_hits"]}))
"""


def _pipeline_compiles_after_boot(stderr: str) -> int:
    """fork_pipeline trace lines after the boot marker (the whole-
    pipeline jits that starve gossip; micro-op programs — tiny
    dynamic_slice reads etc. — are sub-ms noise and excluded)."""
    after = stderr.split("=== BOOT DONE ===", 1)[-1]
    return sum(
        1 for line in after.splitlines()
        if "fork_pipeline" in line
        and ("Finished tracing" in line or "Compiling" in line)
    )


@pytest.mark.slow
def test_fork_prewarm_compile_counts_cold_vs_warm(tmp_path):
    """The compile-count claim, measured with real process isolation
    (in-process jit caches would mask everything): after a WARM boot —
    recorded caps pre-sized, sched buckets replayed, persistent XLA
    cache populated — the gossip stream triggers ZERO fork_pipeline
    compiles, where the cold run paid one per growth/shape step; both
    reach the identical order."""
    cache = str(tmp_path / "aot")

    def run(mode):
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, cache, mode],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_LOG_COMPILES": "1"},
        )
        assert out.returncode == 0, out.stderr[-2000:]
        stats = json.loads(out.stdout.strip().splitlines()[-1])
        stats["pipeline_compiles"] = _pipeline_compiles_after_boot(
            out.stderr
        )
        return stats

    cold = run("cold")
    warm = run("warm")
    assert warm["consensus"] == cold["consensus"] > 0
    assert cold["pipeline_compiles"] > 0, cold
    assert warm["pipeline_compiles"] == 0, (cold, warm)
    assert warm["cache_hits"] > 0, warm


def test_wide_prewarm_compiles_at_boot_and_is_a_semantic_noop(tmp_path):
    cache = str(tmp_path / "aot")
    dag = random_gossip_dag(4, 60, seed=23)

    w1 = WideHashgraph(dag.participants, verify_signatures=False,
                       e_cap=512, s_cap=128, r_cap=16)
    res = aot.prewarm_engine(w1, cache)
    # a fresh cfg's fixed-shape programs compile AT BOOT, not on the
    # first live flush
    assert res["compiled"] > 0
    assert any(e.get("kind") == "wide" for e in aot.load_manifest(cache))

    w2 = WideHashgraph(dag.participants, verify_signatures=False,
                       e_cap=512, s_cap=128, r_cap=16)
    _drive(w1, dag)
    _drive(w2, dag)
    assert w1.consensus_events() == w2.consensus_events()
    assert len(w1.consensus_events()) > 0


@pytest.fixture
def restore_cache_config():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    cc.reset_cache()


def test_configure_follows_env_else_fixed_checkout_path(
        tmp_path, monkeypatch, restore_cache_config):
    """One choice of compile-cache directory for every surface:
    JAX_COMPILATION_CACHE_DIR when set; otherwise the same path inside
    the checkout on every call (a path that moves never hits)."""
    import jax

    env_dir = str(tmp_path / "env_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert aot.configure() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert aot.configure(str(tmp_path / "explicit")) == str(
        tmp_path / "explicit")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = aot.configure()
    assert aot.configure() == first == aot.DEFAULT_CACHE_DIR
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(first) == root
    assert jax.config.jax_compilation_cache_dir == first


def test_configure_outside_a_checkout(tmp_path, monkeypatch,
                                      restore_cache_config):
    """An installed package has no checkout: no default directory, the
    caller's fallback (a node's datadir) is used, and a directory that
    cannot be made turns the cache off instead of failing the start."""
    import jax

    site = tmp_path / "site-packages"
    module = site / "babble_tpu" / "ops" / "aot.py"
    assert aot._checkout_cache_dir(str(module)) is None
    (site / "pyproject.toml").parent.mkdir(parents=True)
    (site / "pyproject.toml").write_text("")
    assert aot._checkout_cache_dir(str(module)) == str(site / ".jax_cache")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(aot, "DEFAULT_CACHE_DIR", None)
    datadir_cache = str(tmp_path / "datadir" / "jax_cache")
    assert aot.configure(fallback=datadir_cache) == datadir_cache
    assert jax.config.jax_compilation_cache_dir == datadir_cache
    blocked = tmp_path / "a_file"
    blocked.write_text("")
    assert aot.configure(str(blocked / "cache")) == ""
    assert aot.configure() == ""
