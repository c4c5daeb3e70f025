"""The comparison behind ``correct``: the program's consensus decisions
against the plain reference (native.consensus), event by event.

Decisions are per event: round, witness, fame (-1 not a witness,
0 undecided, 1 famous, 2 not famous), round received (-1 undecided) and
consensus timestamp.  A whole DAG is ordered at once, so every event is
compared on every field, the timestamp where the reference ordered the
event.  Every count returned is of events that differ, so each limit
is 0."""

from __future__ import annotations

from typing import Dict

import numpy as np

FIELDS = ("round", "witness", "fame", "rr", "cts")


def fame_per_event(wslot: np.ndarray, famous: np.ndarray, e: int) -> np.ndarray:
    """Per-event fame from an engine's [R, N] witness-slot and trilean
    tables (0 undecided, 1 famous, 2 not famous); -1 for non-witnesses."""
    fame = np.full(e, -1, np.int8)
    has = (wslot >= 0) & (wslot < e)
    fame[wslot[has]] = famous[has]
    return fame


def _differ(ref: Dict[str, np.ndarray], got: Dict[str, np.ndarray],
            e: int) -> Dict[str, np.ndarray]:
    out = {k: np.asarray(got[k])[:e].astype(ref[k].dtype) != ref[k][:e]
           for k in FIELDS}
    out["cts"] &= ref["rr"][:e] >= 0
    return out


def mismatches(ref: Dict[str, np.ndarray], got: Dict[str, np.ndarray],
               e: int) -> Dict[str, int]:
    """Events among the first ``e`` whose decision differs, by field."""
    return {k: int(np.count_nonzero(v)) for k, v in _differ(ref, got, e).items()}


def events_differing(ref: Dict[str, np.ndarray], got: Dict[str, np.ndarray],
                     e: int) -> int:
    """Events among the first ``e`` with any decision unlike the
    reference's."""
    bad = np.zeros(e, bool)
    for v in _differ(ref, got, e).values():
        bad |= v
    return int(np.count_nonzero(bad))
