"""Device milliseconds per fixpoint round: the device time of the
fixpoint program's executions in the trace over the rounds the program
counted (sorts, gathers, serve, scatter and the convergence test)."""

from chipbench import trace as tr


def is_fixpoint(program: str) -> bool:
    return "_simulate_fixpoint" in program


def read(rec):
    t, rounds = rec["trace"], rec["counters"].get("rounds", 0)
    if not t or not rounds:
        return None
    ns = tr.module_ns(t, is_fixpoint)
    if not ns:
        return None
    return ns / 1e6 / rounds
