"""Floating-point operations one whole generation NEEDS of a decoder of
the Xing4.0 kind, from the configuration file's published keys: what the
example's unpadded prompt tokens and its decode steps put through the
MLA projections with the query's low rank, the latent up-projection
(once a token: decompressed in the prefill, absorbed into query and
output in a step), the maps' product of the hyper-connected streams (two
sub-layers a layer), the dense layer, the routers (at their published
width) and the shared expert, the head (once for the prompt, once a
step), one expert for each (token, choice) pair that fell on a HELD
expert, and attention's unmasked pairs at the decompressed sizes (the
absorbed form's wider products are how a step runs it, not what it
needs). The maps, Sinkhorn's rounds and the mixes are elementwise and
count nothing here. Padding, padded batch rows and absent experts need
nothing."""

import pathlib

from perfbench.metrics import load_file

_flash = load_file(pathlib.Path(__file__).parent / "_flash_kernel.py")


def per_token_flops(config: dict) -> float:
    """Matrix work of one token through every layer: attention's pairs,
    routed experts and head apart."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    streams = config["hc_mult"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    up = config["qk_nope_head_dim"] + config["v_head_dim"]
    mixer = (2.0 * d * config["q_lora_rank"]
             + 2.0 * config["q_lora_rank"] * heads * qk
             + 2.0 * d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
             + 2.0 * config["kv_lora_rank"] * heads * up
             + 2.0 * heads * config["v_head_dim"] * d)
    maps = 2 * 2.0 * streams * d * streams * (streams + 2)
    total = 0.0
    for ffn in config["ffn_types"][:config["layers"]]:
        total += mixer + maps
        if ffn == "dense":
            total += 2.0 * 3 * d * config["intermediate_size"]
        else:
            total += 2.0 * d * config["published"]["n_routed_experts"]
            total += (2.0 * 3 * d * config["moe_intermediate_size"]
                      * config["n_shared_experts"])
    return total


def attention_flops(config: dict, length: int, steps: int) -> float:
    """The unmasked pairs of the prompt and of each step's one query row,
    in every layer, at the decompressed sizes."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (config["layers"] * 2.0 * (qk + config["v_head_dim"])
            * config["num_attention_heads"] * _flash.pairs(length + steps))


def needed_flops(config: dict, *, length: int, steps: int,
                 held_pairs: int) -> float:
    """One example: `length` prompt tokens, `steps` decode steps,
    `held_pairs` (token, choice) pairs on held experts over both."""
    d = config["hidden_size"]
    expert = 2.0 * 3 * d * config["moe_intermediate_size"]
    head = 2.0 * d * config["vocab_size"]
    return ((length + steps) * per_token_flops(config)
            + held_pairs * expert + (1 + steps) * head
            + attention_flops(config, length, steps))


def stream_bytes(config: dict, stream_rows: int) -> float:
    """What the residual streams' own traffic comes to for `stream_rows`
    (token, sub-layer) rows: the float32 streams read for the maps and
    the pre-mix, read and written by the post-mix."""
    return 3.0 * stream_rows * config["hc_mult"] * config["hidden_size"] * 4
