"""Compilations the program counted while the train window's loop ran
(LoopCounters: one per jit cache miss, a backend compile or a read from
the persistent cache)."""


def read(rec):
    loop = rec.get("loop")
    if not loop:
        return None
    return len(loop["compiles"])
