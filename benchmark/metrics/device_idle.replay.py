"""Device: idle share of the traced window, 100 * (1 - busy / window),
from the profiler trace."""


def read(r: dict):
    tr = r.get("trace")
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None
