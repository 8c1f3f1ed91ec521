"""Device ms per train step in the `bag_grad_sums` scope: the sparse
backward's sums of the pooled bag gradients per unique row."""
from bench import layers


def read(rec):
    return layers.layer_ms(rec, "bag_grad_sums")
