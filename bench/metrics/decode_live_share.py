"""Live rows over arena slots across the window's decode waves: the fused
kernel steps the whole masked arena, so the rest is work on empty slots."""
from bench.reduce import in_window, share


def read(rec):
    ev = in_window(rec, "decode")
    return share(sum(e["rows"] for e in ev), len(ev) * rec["max_slots"])
