"""The train step's embedding gather (Pallas `move_rows`): its least time
for the step's unique rows (bench/flops.gather_bytes, memory bound) over
its device time per call."""
from bench import flops


def read(rec):
    secs, calls = rec.get("kernels", {}).get("move_rows", (0.0, 0))
    rows = rec.get("unique_rows")
    if not calls or not rows:
        return None
    peak = flops.peaks(rec["device_kind"])
    least = sum(flops.least_seconds(0, flops.gather_bytes(rec["cfg"], u),
                                    peak)[0] for u in rows) / len(rows)
    return 100.0 * least / (secs / calls)
