"""Share of the traced window in which no operation ran on the device
(averaged over the chips used), in percent."""

from chipbench import trace as tr


def read(rec):
    t = rec["trace"]
    if not t or not t["devices"]:
        return None
    lo, hi = tr.window(t)
    return 100.0 * (1.0 - tr.busy_ns(t) / (hi - lo))
