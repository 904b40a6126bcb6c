"""1 minus the union of the device's leaf operations over the traced
dispatches' span, on the worst device."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * tr["idle_share_worst"]
