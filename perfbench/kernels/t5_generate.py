"""Floating-point operations one whole generation NEEDS of an
encoder-decoder of the T5 kind, from the configuration file's published
keys: the example's unpadded input tokens through the encoder's
matrices, each decoder layer's cross-attention K and V projections over
the same tokens ONCE, every decode step through the decoder's matrices
and the head, and attention's unmasked pairs (the encoder's square, the
decoder's causal triangle, each step's one query over the input).
Padding, and the rows that pad a batch, need nothing."""


def layer_matrices(config: dict) -> tuple[float, float]:
    """(one attention projection, the dense layer's two matrices): the
    operations of one token through them."""
    d = config["d_model"]
    return (2.0 * d * config["num_heads"] * config["d_kv"],
            2.0 * 2 * d * config["d_ff"])


def pair_flops(config: dict) -> float:
    """One (query, key) pair in every head: its score and its share of
    the weighted sum."""
    return 2.0 * 2 * config["d_kv"] * config["num_heads"]


def needed_flops(config: dict, *, input_tokens: int, steps: int) -> float:
    """One example: `input_tokens` through the encoder, `steps` decode
    steps over them."""
    projection, dense = layer_matrices(config)
    encoders = config["num_layers"]
    decoders = config["assumed"]["num_decoder_layers"]
    pair = pair_flops(config)
    encoder = encoders * (input_tokens * (4 * projection + dense)
                          + pair * input_tokens ** 2)
    cross_kv = decoders * input_tokens * 2 * projection
    # a step: self-attention's four projections, cross-attention's query
    # and output, the dense layer; then the head
    step = (decoders * (6 * projection + dense)
            + 2.0 * config["d_model"] * config["vocab_size"])
    pairs = decoders * pair * (steps * (steps + 1) / 2
                               + steps * input_tokens)
    return encoder + cross_kv + steps * step + pairs
