"""Order (round received, median timestamp): device ms per traced step under the scope path
``babble_order`` (the union of its operations' intervals, from
``benchmark/scopes.py``; the driver fills ``readings["scopes"]``)."""

PATH = "babble_order"


def read(r: dict):
    sc = r.get("scopes")
    if not sc or not r.get("steps_traced") or PATH not in sc["scopes"]:
        return None
    return 1000.0 * sc["scopes"][PATH] / r["steps_traced"]
