"""Forward matmul FLOPs per frame of the TDNN acoustic model."""


def forward_flops_per_frame(cfg):
    """2 x the weights of every layer's matmul: a layer's input is the
    previous layer's output spliced at ``len(ctx)`` offsets, then the
    output layer.  Biases and activations are not counted."""
    h, d, weights = cfg["hidden_dim"], cfg["input_dim"], 0
    for ctx in cfg["tdnn_contexts"]:
        weights += d * len(ctx) * h
        d = h
    weights += d * cfg["num_outputs"]
    return 2 * weights
