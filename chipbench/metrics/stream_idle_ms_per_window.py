"""Device-idle milliseconds per stream window inside the harness's
`simulate_stream` spans: the host's window assembly, chunk verification,
settlement and telemetry fold, as far as the device waits on them."""

from chipbench import trace as tr


def read(rec):
    t, windows = rec["trace"], rec["counters"].get("windows", 0)
    if not t or not t["devices"] or not windows:
        return None
    if not tr.spans_named(t, "simulate_stream"):
        return None
    return tr.idle_in(t, "simulate_stream") / 1e6 / windows
