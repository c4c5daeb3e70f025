"""Ingest, rounds: the fork pipeline's descent-closure iterations per
step, summed over the step's rounds (its ``closure_steps`` counter)."""


def read(r: dict):
    return r.get("closure_steps")
