"""Executables the process built (compiled, or loaded from the compile
cache) inside the window; each is a shape the warm-up missed."""


def read(rec):
    return len(rec["compiles"])
