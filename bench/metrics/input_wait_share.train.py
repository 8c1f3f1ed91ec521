"""Share of the train window the launcher's loop spent waiting in
`next(pipeline)` (host clock, summed by the driver's wrapper)."""


def read(rec):
    if "input_wait_s" not in rec:
        return None
    return 100.0 * rec["input_wait_s"] / rec["window_s"]
