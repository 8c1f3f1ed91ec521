"""The train step's forward interaction (Pallas `dot_interaction`): the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth
(bench/flops.interaction_work; memory bound at these widths) over its
device time per call."""
from bench import flops


def read(rec):
    secs, calls = rec.get("kernels", {}).get("dot_interaction", (0.0, 0))
    if not calls:
        return None
    fl, nbytes = flops.interaction_work(rec["cfg"], rec["batch"])
    least, _ = flops.least_seconds(fl, nbytes,
                                   flops.peaks(rec["device_kind"]))
    return 100.0 * least / (secs / calls)
