"""Native (C++) host components, loaded via ctypes.

The reference is pure Go with no native layer (SURVEY.md §2); here the
performance-critical host-side pieces — bulk DAG generation and level
scheduling for simulation/benchmark scale — are C++, compiled on first use
with the toolchain baked into the image.  Every native entry point has a
pure-Python/numpy fallback with identical output (differentially tested),
so the framework works even without a compiler.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Optional

_DIR = Path(__file__).parent
_BUILD = _DIR / "_build"

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _compile(src: Path, out: Path) -> None:
    out.parent.mkdir(exist_ok=True)
    # build into a temp file then rename: concurrent processes (a testnet
    # fleet booting) must never dlopen a half-written .so
    fd, tmp = tempfile.mkstemp(dir=str(out.parent), suffix=".so")
    os.close(fd)
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        str(src), "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def build_path(name: str) -> Path:
    """Where native/<name>.cpp builds to: keyed on a hash of the source
    contents, so a copied checkout (whose mtimes say nothing) never
    loads a library built from other source."""
    digest = hashlib.sha256((_DIR / f"{name}.cpp").read_bytes()).hexdigest()
    return _BUILD / f"{name}-{digest[:16]}.so"


def _load_lib(name: str) -> Optional[ctypes.CDLL]:
    """Compile (if not built from this source) and dlopen
    native/<name>.cpp."""
    try:
        so = build_path(name)
        if not so.exists():
            _compile(_DIR / f"{name}.cpp", so)
        return ctypes.CDLL(str(so))
    except (OSError, subprocess.SubprocessError):
        return None


def load() -> Optional[ctypes.CDLL]:
    """The graph-builder library, or None if no toolchain is available."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    lib = _load_lib("graph_builder")
    if lib is None:
        return None

    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)

    lib.gossip_dag.restype = ctypes.c_long
    lib.gossip_dag.argtypes = [
        ctypes.c_uint64, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64,
        i32p, i32p, i32p, i32p, i64p, u8p, i32p, i32p,
    ]
    lib.build_schedule.restype = ctypes.c_int32
    lib.build_schedule.argtypes = [
        i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, i32p, i32p,
    ]
    lib.max_level_width.restype = ctypes.c_int32
    lib.max_level_width.argtypes = [i32p, ctypes.c_int64, ctypes.c_int32, i32p]

    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


_baseline_lib: Optional[ctypes.CDLL] = None
_baseline_tried = False


def load_baseline() -> Optional[ctypes.CDLL]:
    """The C++ reference-algorithm consensus baseline (the tests' reference)."""
    global _baseline_lib, _baseline_tried
    if _baseline_lib is not None or _baseline_tried:
        return _baseline_lib
    _baseline_tried = True
    lib = _load_lib("baseline_consensus")
    if lib is None:
        return None

    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i8p = ctypes.POINTER(ctypes.c_int8)

    lib.baseline_consensus.restype = ctypes.c_int64
    lib.baseline_consensus.argtypes = [
        ctypes.c_int32, ctypes.c_int64,
        i32p, i32p, i32p, i32p, i64p, u8p,
        i32p, u8p, i32p, i64p, i8p,
    ]
    _baseline_lib = lib
    return _baseline_lib


def baseline_consensus(dag):
    """Run the C++ reference-algorithm pipeline over an ArrayDag.

    Returns (ordered_count, dict of output arrays) or None when no
    toolchain is available.  This is the honest same-machine baseline the
    benchmark compares against (BASELINE.md's re-measurement requirement);
    correctness is differentially tested against the TPU engine."""
    import numpy as np

    lib = load_baseline()
    if lib is None:
        return None
    e = int(dag.n_events)
    rnd = np.empty(e, np.int32)
    wit = np.empty(e, np.uint8)
    rr = np.empty(e, np.int32)
    cts = np.empty(e, np.int64)
    fame = np.empty(e, np.int8)

    def p(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    sp = np.ascontiguousarray(dag.sp, np.int32)
    op = np.ascontiguousarray(dag.op, np.int32)
    creator = np.ascontiguousarray(dag.creator, np.int32)
    seq = np.ascontiguousarray(dag.seq, np.int32)
    ts = np.ascontiguousarray(dag.ts, np.int64)
    mbit = np.ascontiguousarray(dag.mbit, np.uint8)
    ordered = lib.baseline_consensus(
        int(dag.n), e,
        p(sp, ctypes.c_int32), p(op, ctypes.c_int32),
        p(creator, ctypes.c_int32), p(seq, ctypes.c_int32),
        p(ts, ctypes.c_int64), p(mbit, ctypes.c_uint8),
        p(rnd, ctypes.c_int32), p(wit, ctypes.c_uint8),
        p(rr, ctypes.c_int32), p(cts, ctypes.c_int64),
        p(fame, ctypes.c_int8),
    )
    if ordered < 0:
        return None
    return int(ordered), {
        "round": rnd, "witness": wit.astype(bool), "rr": rr,
        "cts": cts, "fame": fame,
    }
