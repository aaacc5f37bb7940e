"""Device milliseconds per study of the telemetry programs
(`telemetry.attribute_latency` and `telemetry.channel_telemetry`)."""

from chipbench import trace as tr


def is_telemetry(program: str) -> bool:
    return "attribute_latency" in program or "channel_telemetry" in program


def read(rec):
    t, units = rec["trace"], rec["counters"].get("units", 0)
    if not t or not units:
        return None
    ns = tr.module_ns(t, is_telemetry)
    if not ns:
        return None
    return ns / 1e6 / units
