"""Set-up time: process start to the first measured instant (host clock)."""


def read(r: dict):
    return r.get("setup_s")
