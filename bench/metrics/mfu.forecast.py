"""Model FLOPs of the window's closed-loop waves over the device time of
the whole closed-loop programs times the chip's peak: bounds the decode
path even where a later change removes its kernel."""
from bench import flops_bytes
from bench.reduce import DECODE_PROGRAM, in_window, program_seconds, share


def read(rec):
    busy = program_seconds(rec, DECODE_PROGRAM)
    if busy is None:
        return None
    flops = sum(flops_bytes.decode_call(rec["model"], e["rows"],
                                        e["tokens"])[0]
                for e in in_window(rec, "decode"))
    return share(flops, busy * rec["peaks"]["flops_per_s"])
