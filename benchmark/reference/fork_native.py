"""Build and call the benchmark's fork pieces: the generator of DAGs in
which validators equivocate (fork_dag.cpp) and the fork-aware plain
reference (fork_consensus.cpp).

Each library builds once per source into ``benchmark/.build/`` (listed
in .gitignore), keyed on a hash of its source, as ``native.py`` builds
its own."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Dict, Tuple

import numpy as np

from benchmark.reference.native import BASE_TS, BUILD, HERE, _p, i8p, i32p, \
    i64p, u8p

_libs: Dict[str, ctypes.CDLL] = {}


def _lib(name: str) -> ctypes.CDLL:
    if name in _libs:
        return _libs[name]
    src = os.path.join(HERE, f"{name}.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(BUILD, f"{name}-{digest}.so")
    if not os.path.exists(so):
        os.makedirs(BUILD, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=BUILD, suffix=".so")
        os.close(fd)
        try:
            subprocess.run(["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                            src, "-o", tmp],
                           check=True, capture_output=True, timeout=300)
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    lib = ctypes.CDLL(so)
    if name == "fork_dag":
        lib.fork_dag.restype = ctypes.c_long
        lib.fork_dag.argtypes = [
            ctypes.c_uint64, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64,
            i32p, i32p, i32p, i32p, i64p, u8p, i32p, i32p,
        ]
    else:
        lib.fork_reference_consensus.restype = ctypes.c_int64
        lib.fork_reference_consensus.argtypes = [
            ctypes.c_int32, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            i32p, i32p, i32p, i32p, i64p, u8p,
            i32p, u8p, i32p, i64p, i8p,
        ]
    _libs[name] = lib
    return lib


def fork_dag(n: int, n_events: int, seed: int, forkers: int = 1,
             ts_granularity_ns: int = 1_000) -> Dict[str, np.ndarray]:
    """The seeded gossip DAG in which ``forkers`` validators equivocate
    once each, as arrays: sp, op, creator, seq, ts, mbit, levels (slot
    order is topological) and forkers (the equivocators' ids)."""
    if not 0 <= forkers < n or n_events < n:
        raise ValueError(f"cannot draw {forkers} forkers of {n} validators "
                         f"in {n_events} events")
    lib = _lib("fork_dag")
    a = {k: np.empty(n_events, np.int32)
         for k in ("sp", "op", "creator", "seq", "levels")}
    a["ts"] = np.empty(n_events, np.int64)
    a["forkers"] = np.empty(max(forkers, 1), np.int32)[:forkers]
    mbit = np.empty(n_events, np.uint8)
    rc = lib.fork_dag(
        ctypes.c_uint64(seed & ((1 << 64) - 1)), n, n_events, forkers,
        ts_granularity_ns, BASE_TS,
        _p(a["sp"], ctypes.c_int32), _p(a["op"], ctypes.c_int32),
        _p(a["creator"], ctypes.c_int32), _p(a["seq"], ctypes.c_int32),
        _p(a["ts"], ctypes.c_int64), _p(mbit, ctypes.c_uint8),
        _p(a["levels"], ctypes.c_int32), _p(a["forkers"], ctypes.c_int32),
    )
    if rc < 0:
        raise RuntimeError("the generator refused its arguments")
    a["mbit"] = mbit.astype(bool)
    return a


def consensus(dag: Dict[str, np.ndarray], n: int, ts_rule: int = 0,
              fork_blind: bool = False) -> Tuple[int, Dict[str, np.ndarray]]:
    """Every event's round, witness, fame (-1 not a witness, 0 undecided,
    1 famous, 2 not famous), round received (-1 undecided) and consensus
    timestamp, fork-aware.  ``ts_rule`` 1 (mean timestamps) and
    ``fork_blind`` (see is plain ancestry) are the controls."""
    lib = _lib("fork_consensus")
    e = len(dag["sp"])
    out = {"round": np.empty(e, np.int32), "witness": np.empty(e, np.uint8),
           "rr": np.empty(e, np.int32), "cts": np.empty(e, np.int64),
           "fame": np.empty(e, np.int8)}
    args = [np.ascontiguousarray(dag[k], t) for k, t in (
        ("sp", np.int32), ("op", np.int32), ("creator", np.int32),
        ("seq", np.int32), ("ts", np.int64), ("mbit", np.uint8))]
    ordered = lib.fork_reference_consensus(
        n, e, ts_rule, int(fork_blind),
        *(_p(a, t) for a, t in zip(args, (
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_uint8))),
        _p(out["round"], ctypes.c_int32), _p(out["witness"], ctypes.c_uint8),
        _p(out["rr"], ctypes.c_int32), _p(out["cts"], ctypes.c_int64),
        _p(out["fame"], ctypes.c_int8),
    )
    if ordered < 0:
        raise RuntimeError("the reference refused its input")
    out["witness"] = out["witness"].astype(bool)
    return int(ordered), out
