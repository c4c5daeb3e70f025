"""Batch step: device busy time per step in the traced part of the
window."""


def read(r: dict):
    tr = r.get("trace")
    if not tr or not r.get("steps_traced"):
        return None
    return 1000.0 * tr["busy_s"] / r["steps_traced"]
