"""Rounds per fixpoint (`engine._simulate_fixpoint`): the program's own
round counter (`Schedule.rounds`, or a stream's `rounds` over its
windows), per fixpoint resolved in the window."""


def read(rec):
    c = rec["counters"]
    if not c.get("fixpoint_calls"):
        return None
    return c["rounds"] / c["fixpoint_calls"]
