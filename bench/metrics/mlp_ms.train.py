"""Device ms per train step in the `bottom_mlp` and `top_mlp` scopes,
forward and backward."""
from bench import layers


def read(rec):
    return layers.layer_ms(rec, "bottom_mlp", "top_mlp")
