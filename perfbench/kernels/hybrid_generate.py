"""Floating-point operations one whole generation NEEDS of a decoder of
the Granite-4.0-H kind, from the configuration file's published keys:
what the example's unpadded prompt tokens and its decode steps put
through the state-space and attention projections, the convolution, the
routers (at their published width) and the shared expert, the head (once
for the prompt, once a step), one expert for each (token, choice) pair
that fell on a HELD expert, the chunked scan's own products for the
prompt and the one-token step's for each decode step, and attention's
unmasked pairs. Padding, padded batch rows and absent experts need
nothing."""

import pathlib

from perfbench.metrics import load_file

_HERE = pathlib.Path(__file__).parent
_flash = load_file(_HERE / "_flash_kernel.py")
_ssd = load_file(_HERE / "_ssd_kernel.py")
_step = load_file(_HERE / "_ssm_step_kernel.py")


def layer_kinds(config: dict) -> list[str]:
    return list(config["layer_types"][:config["layers"]])


def ssm_shape(config: dict) -> dict:
    return {"heads": config["mamba_n_heads"],
            "head_dim": config["mamba_d_head"],
            "state": config["mamba_d_state"]}


def per_token_flops(config: dict) -> float:
    """Matrix work of one token through every layer: scan, step, routed
    experts and head apart."""
    d = config["hidden_size"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    head_dim = d // heads
    total = 0.0
    for kind in layer_kinds(config):
        if kind == "mamba":
            total += (2.0 * d * (inner + conv + config["mamba_n_heads"])
                      + 2.0 * config["mamba_d_conv"] * conv
                      + 2.0 * inner * d)
        else:
            total += 2.0 * d * (heads + 2 * kv) * head_dim \
                + 2.0 * heads * head_dim * d
        total += 2.0 * d * config["published"]["num_local_experts"]
        total += 2.0 * 3 * d * config["shared_intermediate_size"]
    return total


def mixer_flops(config: dict, length: int, steps: int) -> float:
    """The mixers' own products: the scan over the prompt and a step a
    decode step in each state-space layer, the unmasked pairs of the
    prompt and of each step's one query row in each attention layer."""
    kinds = layer_kinds(config)
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    scan, _ = _ssd.ops_and_bytes(length=length,
                                 chunk=config["mamba_chunk_size"],
                                 **ssm_shape(config))
    step, _ = _step.ops_and_bytes(**ssm_shape(config))
    return (kinds.count("mamba") * (scan + steps * step)
            + kinds.count("attention") * 2.0 * 2 * head_dim * heads
            * _flash.pairs(length + steps))


def needed_flops(config: dict, *, length: int, steps: int,
                 held_pairs: int) -> float:
    """One example: `length` prompt tokens, `steps` decode steps,
    `held_pairs` (token, choice) pairs on held experts over both."""
    d = config["hidden_size"]
    expert = 2.0 * 3 * d * config["intermediate_size"]
    head = 2.0 * d * config["vocab_size"]
    return ((length + steps) * per_token_flops(config)
            + held_pairs * expert + (1 + steps) * head
            + mixer_flops(config, length, steps))
