"""Floating-point operations one whole generation NEEDS of a decoder of
the Ling-3.0 kind, from the configuration file's published keys: what the
example's unpadded prompt tokens and its decode steps put through the
KDA and MLA projections, the convolution, the latent up-projection (once
a token: decompressed in the prefill, absorbed into query and output in
a step), the dense layer, the routers (at their published width) and the
shared expert, the head (once for the prompt, once a step), one expert
for each (token, choice) pair that fell on a HELD expert, the delta
rule's recurrence a token a KDA layer (the chunked form's extra products
are how the prefill runs it, not what it needs), and attention's
unmasked pairs at the decompressed sizes (the absorbed form's wider
products likewise). Padding, padded batch rows and absent experts need
nothing."""

import pathlib

from perfbench.metrics import load_file

_HERE = pathlib.Path(__file__).parent
_flash = load_file(_HERE / "_flash_kernel.py")
_step = load_file(_HERE / "_kda_step_kernel.py")


def kda_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "head_dim": config["head_dim"]}


def mla_shape(config: dict) -> dict:
    """The flash kernel's view of the MLA layer's prefill."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_attention_heads"],
            "d_qk": config["qk_head_dim"], "d_v": config["v_head_dim"]}


def per_token_flops(config: dict) -> float:
    """Matrix work of one token through every layer: the delta rule,
    attention's pairs, routed experts and head apart."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    hk = heads * config["head_dim"]
    n = config["layers"]
    total = 0.0
    for kind, ffn in zip(config["layer_types"][:n], config["ffn_types"][:n]):
        if kind == "kda":
            total += (2.0 * d * (4 * hk + 2 * heads)
                      + 2.0 * config["short_conv_kernel_size"] * 3 * hk
                      + 2.0 * hk * d)
        else:
            up = config["qk_nope_head_dim"] + config["v_head_dim"]
            total += (2.0 * d * (heads * config["qk_head_dim"]
                                 + config["kv_lora_rank"]
                                 + config["qk_rope_head_dim"] + heads)
                      + 2.0 * config["kv_lora_rank"] * heads * up
                      + 2.0 * heads * config["v_head_dim"] * d)
        if ffn == "dense":
            total += 2.0 * 3 * d * config["intermediate_size"]
        else:
            total += 2.0 * d * config["published"]["num_experts"]
            total += 2.0 * 3 * d * config["moe_shared_expert_intermediate_size"]
    return total


def mixer_flops(config: dict, length: int, steps: int) -> float:
    """The mixers' own: the recurrence a token in each KDA layer, the
    unmasked pairs of the prompt and of each step's one query row in each
    MLA layer."""
    kinds = list(config["layer_types"][:config["layers"]])
    step, _ = _step.ops_and_bytes(**kda_shape(config))
    shape = mla_shape(config)
    return (kinds.count("kda") * (length + steps) * step
            + kinds.count("mla") * 2.0 * (shape["d_qk"] + shape["d_v"])
            * shape["heads"] * _flash.pairs(length + steps))


def needed_flops(config: dict, *, length: int, steps: int,
                 held_pairs: int) -> float:
    """One example: `length` prompt tokens, `steps` decode steps,
    `held_pairs` (token, choice) pairs on held experts over both."""
    d = config["hidden_size"]
    expert = 2.0 * 3 * d * config["moe_intermediate_size"]
    head = 2.0 * d * config["vocab_size"]
    return ((length + steps) * per_token_flops(config)
            + held_pairs * expert + (1 + steps) * head
            + mixer_flops(config, length, steps))
