"""Share of its roofline that the fused decode kernel reached in the traced
window: the least time of the window's closed-loop waves (``flops_bytes``)
over the kernel's device time."""
from bench.reduce import DECODE_PROGRAM, decode_least_seconds, kernel_seconds
from bench.reduce import share


def read(rec):
    busy = kernel_seconds(rec, DECODE_PROGRAM)
    if busy is None:
        return None
    return share(decode_least_seconds(rec), busy)
