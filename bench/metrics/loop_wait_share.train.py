"""Share of the train window the launcher's loop spent in its own
`train.next_batch` phase (host clock, the program's LoopCounters)."""


def read(rec):
    loop = rec.get("loop")
    if not loop:
        return None
    return 100.0 * loop["phase_s"]["next_batch"] / rec["window_s"]
