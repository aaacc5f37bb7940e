"""The serve-round kernel's share of its roofline, in percent.

The least time the chip could take for the serve rounds of the window is
the bytes they must move over the HBM bandwidth of the peaks table.  A
round over K = N*H items reads, per item, the six int32 components of its
(max,+) map and writes its int32 departure: 28 bytes per item.  The count
depends only on the round's shape, so it stays the same work whatever
implements the round.  The scan's integer operations run on the vector
unit, whose integer peak is not published; bytes bound the roofline.

Kernel time: the device time of the Mosaic kernel's events in the trace:
the custom calls named after the kernel's entry, ``serve_scan.<n>`` (one
per round).  No such events: nothing to read.
"""

from chipbench import trace as tr

BYTES_PER_ITEM = 6 * 4 + 4
KERNEL = "serve_scan"


def serve_bytes(items: int) -> int:
    """Bytes the serve rounds over ``items`` items (rounds x K) move."""
    return BYTES_PER_ITEM * items


def is_kernel(op: str, program: str) -> bool:
    return op.split(".")[0] == KERNEL


def read(rec):
    t, items = rec["trace"], rec["counters"].get("serve_items", 0)
    if not t or not items or rec["peak"] is None:
        return None
    ns = tr.op_ns(t, is_kernel)
    if not ns:
        return None
    least_s = serve_bytes(items) / rec["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
