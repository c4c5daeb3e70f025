"""Build and call the benchmark's C++ pieces: the DAG generator
(gossip_dag.cpp) and the plain consensus reference (consensus.cpp).

Each library builds once per source into ``benchmark/.build/`` (listed
in .gitignore), keyed on a hash of its source, so a copied checkout
never loads a library built from other source."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Dict, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BUILD = os.path.join(os.path.dirname(HERE), ".build")

#: start of the generated DAG's timestamps (ns), as the program's
#: sim.arrays uses, so both build the same DAG from one seed
BASE_TS = 1_700_000_000_000_000_000

_libs: Dict[str, ctypes.CDLL] = {}

i32p = ctypes.POINTER(ctypes.c_int32)
i64p = ctypes.POINTER(ctypes.c_int64)
u8p = ctypes.POINTER(ctypes.c_uint8)
i8p = ctypes.POINTER(ctypes.c_int8)


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
    if name == "gossip_dag":
        lib.gossip_dag.restype = ctypes.c_long
        lib.gossip_dag.argtypes = [
            ctypes.c_uint64, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64,
            i32p, i32p, i32p, i32p, i64p, u8p, i32p, i32p,
        ]
    else:
        lib.reference_consensus.restype = ctypes.c_int64
        lib.reference_consensus.argtypes = [
            ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
            i32p, i32p, i32p, i32p, i64p, u8p,
            i32p, u8p, i32p, i64p, i8p,
        ]
    _libs[name] = lib
    return lib


def _p(a: np.ndarray, t):
    return a.ctypes.data_as(ctypes.POINTER(t))


def gossip_dag(n: int, n_events: int, seed: int,
               ts_granularity_ns: int = 1_000) -> Dict[str, np.ndarray]:
    """The seeded random-gossip DAG as arrays: sp, op, creator, seq, ts,
    mbit, levels (slot order is topological)."""
    lib = _lib("gossip_dag")
    a = {k: np.empty(n_events, np.int32)
         for k in ("sp", "op", "creator", "seq", "levels")}
    a["ts"] = np.empty(n_events, np.int64)
    mbit = np.empty(n_events, np.uint8)
    heads = np.empty(n, np.int32)
    lib.gossip_dag(
        ctypes.c_uint64(seed & ((1 << 64) - 1)), n, n_events,
        ts_granularity_ns, BASE_TS,
        _p(a["sp"], ctypes.c_int32), _p(a["op"], ctypes.c_int32),
        _p(a["creator"], ctypes.c_int32), _p(a["seq"], ctypes.c_int32),
        _p(a["ts"], ctypes.c_int64), _p(mbit, ctypes.c_uint8),
        _p(a["levels"], ctypes.c_int32), _p(heads, ctypes.c_int32),
    )
    a["mbit"] = mbit.astype(bool)
    return a


def consensus(dag: Dict[str, np.ndarray], n: int,
              ts_rule: int = 0) -> Tuple[int, Dict[str, np.ndarray]]:
    """Every event's round, witness, fame (-1 not a witness, 0 undecided,
    1 famous, 2 not famous), round received (-1 undecided) and consensus
    timestamp.  ``ts_rule`` 1 is the control (mean timestamps)."""
    lib = _lib("consensus")
    e = len(dag["sp"])
    out = {"round": np.empty(e, np.int32), "witness": np.empty(e, np.uint8),
           "rr": np.empty(e, np.int32), "cts": np.empty(e, np.int64),
           "fame": np.empty(e, np.int8)}
    args = [np.ascontiguousarray(dag[k], t) for k, t in (
        ("sp", np.int32), ("op", np.int32), ("creator", np.int32),
        ("seq", np.int32), ("ts", np.int64), ("mbit", np.uint8))]
    ordered = lib.reference_consensus(
        n, e, ts_rule,
        _p(args[0], ctypes.c_int32), _p(args[1], ctypes.c_int32),
        _p(args[2], ctypes.c_int32), _p(args[3], ctypes.c_int32),
        _p(args[4], ctypes.c_int64), _p(args[5], ctypes.c_uint8),
        _p(out["round"], ctypes.c_int32), _p(out["witness"], ctypes.c_uint8),
        _p(out["rr"], ctypes.c_int32), _p(out["cts"], ctypes.c_int64),
        _p(out["fame"], ctypes.c_int8),
    )
    if ordered < 0:
        raise RuntimeError("the reference refused its input")
    out["witness"] = out["witness"].astype(bool)
    return int(ordered), out
