"""Floating-point operations one whole generation NEEDS of a decoder of
the MiMo-V2 kind, from the configuration file's published keys: what the
example's unpadded prompt tokens and its decode steps put through the
attention and dense matrices, the routers, the head (once for the
prompt, once a step), one expert for each (token, choice) pair that fell
on a HELD expert, and attention's unmasked pairs. Padding, padded batch
rows and absent experts need nothing."""

import pathlib

from perfbench.metrics import load_file

_flash = load_file(pathlib.Path(__file__).parent / "_flash_kernel.py")


def layer_kinds(config: dict) -> list[tuple[bool, bool]]:
    """(window?, experts?) of each layer that runs."""
    n = config["layers"]
    return list(zip((bool(v) for v in config["hybrid_layer_pattern"][:n]),
                    (bool(v) for v in config["moe_layer_freq"][:n])))


def kv_heads(config: dict, windowed: bool) -> int:
    return config["swa_num_key_value_heads" if windowed
                  else "num_key_value_heads"]


def per_token_flops(config: dict) -> float:
    """Matrix work of one token through every layer, experts and head
    apart."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    dk, dv = config["head_dim"], config["v_head_dim"]
    total = 0.0
    for windowed, experts in layer_kinds(config):
        kv = kv_heads(config, windowed)
        total += 2.0 * d * (h * dk + kv * (dk + dv)) + 2.0 * h * dv * d
        if experts:
            total += 2.0 * d * config["published"]["n_routed_experts"]
        else:
            total += 2.0 * 3 * d * config["intermediate_size"]
    return total


def attention_pairs(config: dict, length: int, steps: int) -> float:
    """Unmasked pairs times heads times 2 (dk + dv): the prompt's, then
    each step's one query row over what it may see."""
    h = config["num_attention_heads"]
    width = 2.0 * (config["head_dim"] + config["v_head_dim"]) * h
    total = 0.0
    for windowed, _ in layer_kinds(config):
        window = config["sliding_window"] if windowed else None
        # a step's query is one more row of the same causal triangle
        total += width * _flash.pairs(length + steps, window)
    return total


def needed_flops(config: dict, *, length: int, steps: int,
                 held_pairs: int) -> float:
    """One example: `length` prompt tokens, `steps` decode steps,
    `held_pairs` (token, choice) pairs on held experts over both."""
    d = config["hidden_size"]
    expert = 2.0 * 3 * d * config["moe_intermediate_size"]
    head = 2.0 * d * config["vocab_size"]
    return ((length + steps) * per_token_flops(config)
            + held_pairs * expert + (1 + steps) * head
            + attention_pairs(config, length, steps))
