"""The names the simulator puts on a profiler trace (`jax.profiler.trace`).

Host spans (`jax.profiler.TraceAnnotation`, on the trace's host plane and
on the same clock as the device's events), one per phase per call, never
one per request, row or item:

  * ``lower.*``: `devices.build_workload`: the request rows, the route
    resolution, the hop-table fill with the credit-return DLLP plan, and
    `finish_hops`, the channels and the arrays handed to JAX;
  * ``window.*``: `streaming._process_window`: window assembly up to the
    `StreamCarry`; the fixpoint with its convergence read-back and the
    schedule pulls; the settlement masks with the frontier advance; the
    telemetry fold with the stall replay; the peak-backlog event merge;
    the carried-row extraction with the join seeds.

Device scopes (`jax.named_scope`, in the ``op_name`` metadata of the
compiled program's operations): the steps of `engine._one_round`, the sort
order (``round.order``), the gathers into sorted order and of the carried
seeds (``round.gather``), the serve scan or kernel with its fallback
(``round.serve``), and the un-permuting sort with the arrival propagation
(``round.scatter``).

Nothing is recorded unless a profiler trace is running; a scope changes
only the metadata of the compiled program.
"""

NAMES = (
    "lower.requests", "lower.routes", "lower.hops", "lower.finish",
    "window.assemble", "window.resolve", "window.settle", "window.backlog",
    "window.fold", "window.carry",
    "round.order", "round.gather", "round.serve", "round.scatter",
)
