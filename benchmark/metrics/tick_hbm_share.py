"""The least time a tick could take over the time it takes on the
device.  Least: every leaf of the state read once and written once
(``state_bytes``, one device's share on a mesh) at the chip's HBM
bandwidth from ``peaks.json``.  Taken: the traced program runs' device
time over their ticks.  Bound: bytes."""


def least_tick_s(state_bytes: int, chips: int, hbm_bytes_per_s: float):
    return 2.0 * (state_bytes / chips) / hbm_bytes_per_s


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec["traced"] or not rec.get("peaks"):
        return None
    ticks = len(rec["traced"]) * rec["ticks_per_dispatch"]
    taken = tr["module_s"] / ticks
    if taken <= 0:
        return None
    least = least_tick_s(rec["state_bytes"], rec["chips"],
                         rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / taken
