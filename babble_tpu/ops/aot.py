"""AOT compilation cache for the consensus kernels.

Kills the cold-start tax (ROADMAP item 3): every early benchmark config
paid 19-37 s of XLA compile+first-run, one run blew a 1500 s watchdog
on it, and a fleet restart re-paid the whole bill.  Three layers:

1. **Persistent XLA cache** (``configure``): jax's compilation cache
   directory, so a recompile of an already-seen program is a
   deserialize (sub-second) instead of a full XLA pass.  Every
   surface routes through here, so one directory serves them all:
   ``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed path in the
   checkout, else the caller's fallback (a node's datadir).
2. **Shape manifest** (``record_shape`` / ``load_manifest``): the
   engine records every live-flush program it actually compiled —
   keyed on the ``DagConfig`` + ``ENGINE_CACHE_VERSION`` + the bucketed
   batch/window shape — into ``babble_aot_manifest.json`` beside the
   cache.  A restart replays the manifest BEFORE the first flush.
3. **AOT executables** (``prewarm_engine``): each manifest entry is
   ``jit(...).lower(...).compile()``-d against abstract
   ``ShapeDtypeStruct`` inputs and parked in the engine's ``_aot`` map,
   so the first live flush calls a ready executable — no trace, no
   dispatch-path compile, and (warm) the XLA work is a cache
   deserialize.

Compile visibility: ``bind_registry`` maps jax's monitoring events onto
``babble_compile_cache_hits_total`` / ``_misses_total`` /
``babble_xla_compiles_total``, and ``compile_counts()`` exposes the
same numbers to tests (the compile-count regression suite asserts a
same-shape flush stream triggers zero of them).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .state import DagConfig, init_state

#: bump when a change to the flush/ingest/fame/order kernels makes old
#: manifest entries meaningless (the persistent XLA cache keys on HLO
#: and self-invalidates; this guards OUR shape replay layer).
#: 9.0: kernel working-set diet — live-flush keys grew the frontier
#: bucket F ((W, F, gate, kpad, t, b)) and DagConfig the packed flag.
ENGINE_CACHE_VERSION = "9.0"

_MANIFEST = "babble_aot_manifest.json"

log = logging.getLogger("babble_tpu.aot")

# ----------------------------------------------------------------------
# compile-event counters (jax.monitoring -> obs registries + tests)

_stats = {"cache_hits": 0, "cache_misses": 0, "xla_compiles": 0,
          "traces": 0}
_bound: List[dict] = []          # registry counters fed by the listeners
_installed = False


def _on_event(name: str, **kw) -> None:
    key = None
    if name == "/jax/compilation_cache/cache_hits":
        key = "cache_hits"
    elif name == "/jax/compilation_cache/cache_misses":
        key = "cache_misses"
    if key is None:
        return
    _stats[key] += 1
    for b in _bound:
        b[key].inc()


def _on_duration(name: str, dur: float, **kw) -> None:
    key = None
    if name.endswith("backend_compile_duration"):
        key = "xla_compiles"
    elif name.endswith("jaxpr_trace_duration"):
        key = "traces"
    if key is None:
        return
    _stats[key] += 1
    for b in _bound:
        b[key].inc()


def install_listeners() -> None:
    """Register the jax.monitoring listeners once per process (jax has
    no unregister; the listeners fan out to every bound registry)."""
    global _installed
    if _installed:
        return
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def bind_registry(registry) -> None:
    """Expose the compile counters on a node/bench registry."""
    install_listeners()
    _bound.append({
        "cache_hits": registry.counter(
            "babble_compile_cache_hits_total",
            "persistent-compilation-cache hits (XLA compile skipped)"),
        "cache_misses": registry.counter(
            "babble_compile_cache_misses_total",
            "persistent-compilation-cache misses (full XLA compile paid)"),
        "xla_compiles": registry.counter(
            "babble_xla_compiles_total",
            "XLA backend compiles (cache deserializes excluded... "
            "counted per backend_compile event)"),
        "traces": registry.counter(
            "babble_jit_traces_total",
            "jaxpr traces (a same-shape flush stream must add zero)"),
    })


def compile_counts() -> Dict[str, int]:
    """Process-wide compile/trace counters (the regression tests'
    compilation hook).  install_listeners() must have run first."""
    return dict(_stats)


# ----------------------------------------------------------------------
# persistent XLA cache

def _checkout_cache_dir(module_file: str = __file__) -> Optional[str]:
    """``<checkout>/.jax_cache`` (listed in .gitignore) when the package
    runs from a checkout — fixed, because the path is part of what a
    later process must find again; None in an installed package, whose
    directory is neither the user's nor writable."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(module_file))))
    if os.path.isfile(os.path.join(root, "pyproject.toml")):
        return os.path.join(root, ".jax_cache")
    return None


#: the cache directory when neither the caller nor the environment names
#: one (None outside a checkout)
DEFAULT_CACHE_DIR = _checkout_cache_dir()


def configure(cache_dir: Optional[str] = None,
              fallback: Optional[str] = None) -> str:
    """Point jax's persistent compilation cache at the one chosen
    directory and return it: an explicit ``cache_dir`` (cli
    ``--jax_cache``), else ``JAX_COMPILATION_CACHE_DIR``, else
    ``DEFAULT_CACHE_DIR``, else ``fallback`` (a node's datadir cache).
    Every surface — cli, testnet nodes, bench, chip_smoke — routes
    through here so the flags agree.  Returns "" (cache off, with
    a warning) when there is no directory or it cannot be made.
    Idempotent; safe before or after backend init."""
    path = (cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_CACHE_DIR or fallback)
    if not path:
        log.warning("no compile-cache directory: running without one")
        return ""
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as exc:
        log.warning("compile cache off: cannot make %s (%s)", path, exc)
        return ""
    # when the environment names the directory jax has already read it
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    # the live-flush latency program is deliberately small — without
    # this floor it would fall under jax's default 1 s minimum and
    # never persist, which is exactly the program we restart for
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    install_listeners()
    return path


# ----------------------------------------------------------------------
# shape manifest

def _cfg_key(cfg: DagConfig) -> list:
    # JSON round-trips tuples (the membership plane's retired columns)
    # as lists — normalize so manifest comparison survives reload
    return [list(v) if isinstance(v, tuple) else v for v in cfg]


def manifest_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, _MANIFEST)


def load_manifest(cache_dir: str) -> List[dict]:
    try:
        with open(manifest_path(cache_dir)) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return []
    if not isinstance(data, dict) or data.get("version") != \
            ENGINE_CACHE_VERSION:
        return []
    entries = data.get("entries")
    return entries if isinstance(entries, list) else []


def _record_entry(cache_dir: str, entry: dict) -> None:
    """Append one manifest entry (idempotent; best-effort — a
    read-only cache dir only loses prewarm).  The read-modify-replace
    runs under an flock'd sidecar: fleet nodes share one cache dir, and
    without the lock concurrent writers drop each other's entries
    (last-writer-wins), silently re-arming the compile storm the
    manifest exists to kill."""
    try:
        import fcntl

        os.makedirs(cache_dir, exist_ok=True)
        with open(manifest_path(cache_dir) + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            entries = load_manifest(cache_dir)
            if entry in entries:
                return
            entries.append(entry)
            tmp = manifest_path(cache_dir) + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"version": ENGINE_CACHE_VERSION,
                           "entries": entries}, f)
            os.replace(tmp, manifest_path(cache_dir))
    except (OSError, ImportError):
        pass


def record_shape(cache_dir: str, cfg: DagConfig, key: tuple) -> None:
    """Record one compiled fused live-flush shape."""
    _record_entry(cache_dir, {"cfg": _cfg_key(cfg), "key": list(key)})


def record_fork_caps(cache_dir: str, n: int, k: int, caps: tuple,
                     sched: Optional[tuple] = None) -> None:
    """Record a fork pipeline's compiled shape: the monotone capacity
    triple plus the bucketed level-schedule dims (the byzantine engine
    compiles one whole pipeline per (n, k, caps, sched))."""
    entry = {"kind": "fork", "n": int(n), "k": int(k),
             "caps": [int(c) for c in caps]}
    if sched is not None:
        entry["sched"] = [int(s) for s in sched]
    _record_entry(cache_dir, entry)


def record_wide_cfg(cache_dir: str, cfg: DagConfig, n_blocks: int) -> None:
    """Record a wide engine's config + block layout (its fixed-shape
    fame/order/march programs are keyed on exactly this)."""
    _record_entry(cache_dir, {"kind": "wide", "cfg": _cfg_key(cfg),
                              "n_blocks": int(n_blocks)})


# ----------------------------------------------------------------------
# AOT prewarm

#: shapes compiled when the manifest has nothing for this cfg yet: the
#: smallest gossip buckets (an 8-event flush with 1-4 topological
#: levels under the first W bucket and the smallest frontier bucket —
#: a fresh engine's frontier height starts under F_MIN) — the programs
#: a fresh live fleet hits within its first heartbeats
_DEFAULT_SHAPES: Tuple[Tuple[int, Tuple[int, int]], ...] = (
    (8, (1, 4)),
    (8, (2, 4)),
)


def _batch_struct(kpad: int, tb: Tuple[int, int]):
    from .ingest import EventBatch

    sds = jax.ShapeDtypeStruct
    return EventBatch(
        sp=sds((kpad,), jnp.int32),
        op=sds((kpad,), jnp.int32),
        creator=sds((kpad,), jnp.int32),
        seq=sds((kpad,), jnp.int32),
        ts=sds((kpad,), jnp.int64),
        mbit=sds((kpad,), jnp.bool_),
        k=sds((), jnp.int32),
        sched=sds(tuple(tb), jnp.int32),
    )


def prewarm_engine(engine, cache_dir: str,
                   defaults: bool = True,
                   limit: Optional[int] = None) -> Dict[str, int]:
    """AOT-compile the programs this engine will need, by engine kind.

    **Fused** engines replay the manifest's live-flush shape entries
    for this exact (DagConfig, ENGINE_CACHE_VERSION) — plus the default
    gossip shapes when the manifest holds none — into the engine's
    executable map.  **Fork** (byzantine) engines pre-size to the
    manifest's recorded pipeline capacities and run one warmup pass, so
    the whole-pipeline jit happens at boot instead of the first gossip
    tick.  **Wide** engines run one warmup consensus pass over the
    freshly-allocated (empty) state, compiling the fixed-shape
    march/fame/order programs their first real flush would otherwise
    pay for (per-batch coordinate kernels stay demand-compiled — they
    are small and bucket-shared).

    With a populated persistent cache the XLA work is a deserialize,
    so a fleet restart reaches its first flush in seconds; cold, this
    is the same compile the first flush would have paid, just moved
    to boot where it cannot stall gossip.  ``limit`` caps how many
    fused manifest entries prewarm (oldest first — manifest order is
    usage order, so early entries are the shapes the first flushes
    hit); later shapes still deserialize from the persistent cache on
    first use, they just pay their trace mid-stream instead of at boot.

    Returns {"compiled": n, "from_manifest": m}."""
    from . import flush as flush_ops

    # ``cache_dir`` holds the manifest; the caller chose it and pointed
    # the persistent cache there (``configure``)
    install_listeners()
    engine._aot_dir = cache_dir
    if hasattr(engine, "pre_size") and hasattr(engine, "k"):
        return _prewarm_fork(engine, cache_dir)
    if hasattr(engine, "stream"):
        return _prewarm_wide(engine, cache_dir)
    cfg = engine.cfg
    gate = engine.finality_gate

    keys = []
    from_manifest = 0
    for e in load_manifest(cache_dir):
        if e.get("cfg") == _cfg_key(cfg):
            if limit is not None and from_manifest >= limit:
                break
            keys.append(tuple(e["key"]))
            from_manifest += 1
    if not keys and defaults:
        w0 = flush_ops.bucket_w(1, cfg.r_cap)
        # a frontier-off engine's live keys always carry f = e1 —
        # default shapes must match or boot compiles programs the
        # first heartbeats can never hit
        f0 = (flush_ops.bucket_f(1, cfg.e_cap + 1)
              if getattr(engine, "frontier", True) else cfg.e_cap + 1)
        if w0:
            keys = [(w0, f0, gate, kpad) + tb
                    for kpad, tb in _DEFAULT_SHAPES]

    state_sds = jax.eval_shape(lambda: init_state(cfg))
    compiled = 0
    for key in keys:
        if key in engine._aot:
            continue
        w, f, kgate, kpad, t, b = key
        if w > cfg.r_cap or f > cfg.e_cap + 1 or kgate != gate:
            continue
        lowered = flush_ops.live_flush.lower(
            cfg, int(w), int(f), bool(kgate), state_sds,
            _batch_struct(int(kpad), (int(t), int(b))),
        )
        engine._aot[key] = lowered.compile()
        engine._aot_recorded.add(key)
        compiled += 1
    return {"compiled": compiled, "from_manifest": from_manifest}


def _prewarm_fork(engine, cache_dir: str) -> Dict[str, int]:
    """Byzantine-engine prewarm (the KERNEL_SPLIT-gate leftover,
    ROADMAP 3c): pre-size to the largest recorded pipeline capacities
    for this (n, k), then trace-and-compile the pipeline at those caps
    for every recorded (bucketed) level-schedule shape, using synthetic
    empty batches through the REAL jit entry — so a restarted node's
    live ticks hit a warm jit cache (and, across processes, the
    persistent XLA cache) instead of paying whole-pipeline compiles
    mid-gossip.  Shapes are replayed at the MERGED max caps because
    that is what the presized engine will actually call with."""
    import jax.numpy as jnp

    from .forks import ForkBatch, ForkConfig, fork_pipeline

    caps = None
    scheds = set()
    from_manifest = 0
    for e in load_manifest(cache_dir):
        if (e.get("kind") == "fork" and e.get("n") == engine.n
                and e.get("k") == engine.k):
            c = tuple(int(v) for v in e.get("caps", ()))
            if len(c) == 3:
                caps = c if caps is None else tuple(
                    max(a, b) for a, b in zip(caps, c)
                )
                from_manifest += 1
            s = e.get("sched")
            if isinstance(s, list) and len(s) == 2:
                scheds.add((int(s[0]), int(s[1])))
    if caps is None:
        return {"compiled": 0, "from_manifest": 0}
    engine.pre_size(caps)
    cfg = ForkConfig(n=engine.n, k=engine.k, e_cap=caps[0],
                     s_cap=caps[1], r_cap=caps[2])
    e1, B, s1 = cfg.e_cap + 1, cfg.b, cfg.s_cap + 1
    before = _stats["xla_compiles"]
    compiled = 0
    for (t, w) in sorted(scheds):
        batch = ForkBatch(
            sp=jnp.full((e1,), -1, jnp.int32),
            op=jnp.full((e1,), -1, jnp.int32),
            ebr=jnp.full((e1,), B, jnp.int32),
            eseq=jnp.full((e1,), -1, jnp.int32),
            ecr=jnp.full((e1,), cfg.n, jnp.int32),
            ts=jnp.zeros((e1,), jnp.int64),
            mbit=jnp.zeros((e1,), jnp.bool_),
            sched=jnp.full((t, w), -1, jnp.int32),
            cp=jnp.zeros((B, B), jnp.int32),
            ce=jnp.full((B, s1), -1, jnp.int32),
            cnt=jnp.zeros((B,), jnp.int32),
            owner=jnp.zeros((B, s1), jnp.bool_),
            n_events=jnp.asarray(0, jnp.int32),
            rseed=jnp.full((e1,), -1, jnp.int32),
            wseed=jnp.full((e1,), -1, jnp.int8),
            s_off=jnp.zeros((B,), jnp.int32),
        )
        fork_pipeline(cfg, batch)   # populate jit + persistent caches
        compiled += 1
    return {"compiled": compiled,
            "from_manifest": from_manifest,
            "xla_compiles": _stats["xla_compiles"] - before}


def _prewarm_wide(engine, cache_dir: str) -> Dict[str, int]:
    """Wide-engine prewarm (the KERNEL_SPLIT-gate leftover, ROADMAP
    3c): one warmup consensus pass over the freshly-allocated empty
    state compiles the fixed-shape march/fame/order programs.  Fame and
    order over an all-sentinel window are semantic no-ops (no
    witnesses, no decisions), so the warmup cannot perturb consensus —
    differentially covered by the prewarm parity test."""
    from_manifest = sum(
        1 for e in load_manifest(cache_dir)
        if e.get("kind") == "wide" and e.get("cfg") == _cfg_key(engine.cfg)
    )
    record_wide_cfg(cache_dir, engine.cfg, engine.stream.C)
    before = _stats["xla_compiles"]
    engine.stream.consensus(final=False)
    engine.state = engine.stream.state
    engine._view = {}
    return {"compiled": _stats["xla_compiles"] - before,
            "from_manifest": from_manifest}
