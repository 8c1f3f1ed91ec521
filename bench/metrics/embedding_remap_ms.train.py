"""Device ms per train step in the `embedding_remap` scope: the lookup's
searchsorted remap of each slot into the compact row slab, inside the
per-feature scan."""
from bench import layers


def read(rec):
    return layers.layer_ms(rec, "embedding_remap")
