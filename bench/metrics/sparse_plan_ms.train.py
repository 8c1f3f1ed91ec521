"""Device ms per train step in the `sparse_plan` scope: the sparse plan's
argsort and bucketing, in the forward's and the backward's builds."""
from bench import layers


def read(rec):
    return layers.layer_ms(rec, "sparse_plan")
