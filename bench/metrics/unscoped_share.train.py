"""Share of the device's busy time in the traced train window that no
layer scope of the program held (bench/layers.py)."""


def read(rec):
    lay = rec.get("layers")
    if not lay or not lay["layer_s"] or not lay["busy_s"]:
        return None
    return 100.0 * lay["unscoped_s"] / lay["busy_s"]
