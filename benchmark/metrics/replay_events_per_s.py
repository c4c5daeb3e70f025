"""Events brought to full consensus order per second: whole steps in
the window times events per step, over the window's seconds, the last
step included whole (host clock)."""


def read(r: dict):
    if "steps" not in r or not r["steps"]:
        return None
    return r["steps"] * r["events_per_step"] / r["window_s"]
