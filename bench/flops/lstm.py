"""Forward matmul FLOPs per frame of the LSTM acoustic model."""


def forward_flops_per_frame(cfg):
    """2 x the weights of every matmul a frame goes through: each LSTM
    layer's four gates over [x_t, h_{t-1}], the feed-forward layers and
    the output layer.  Biases and gate nonlinearities are not counted."""
    h, d, weights = cfg["hidden_dim"], cfg["input_dim"], 0
    for _ in range(cfg["num_recurrent_layers"]):
        weights += (d + h) * 4 * h
        d = h
    for _ in range(cfg["num_ff_layers"]):
        weights += d * h
        d = h
    weights += d * cfg["num_outputs"]
    return 2 * weights
