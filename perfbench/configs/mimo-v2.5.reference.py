"""MiMo-V2.5, one chip's share: the plain reference and the comparison
that decides `correct` for its cell.

The reference is the published language model's equations in
straightforward jax.numpy, float32 under "highest" matmul precision: ONE
full forward pass over one whole sequence, with no cache, no kernel and
no batching. h0 = E[ids]; a layer is x = h + Attn(RMSNorm(h)), h' = x +
FFN(RMSNorm(x)); a final RMSNorm and an untied head. Attention: one
fused projection gives q (64 heads x 192), k and v (4 K/V heads in a
full layer, 8 in a window layer; 192 and 128 wide); rotary on the first
64 dims of q and k (halves paired), base 1e7 in full layers and 1e4 in
window layers; scores q.k / sqrt(192), causal, in a window layer also
i - j < 128 and one learned sink logit a head in the softmax's
denominator; v times 0.707; query head h reads K/V head h // (64 / kv).
FFN: layer 0 a SwiGLU of width 16384; every other layer s = sigmoid(x
W_r) over all 256 experts, the top 8 of s + b, weights s_e over their
sum, y = sum w_e SwiGLU_e(x) over the experts THIS CHIP HOLDS (the same
share the program is given: `n_routed_experts` experts from
`deployment.expert_offset`; what the absent experts would add is left
out here as there). Logits and argmax are over the vocabulary's slice.

It is computed layer by layer (one layer's weights in float32 at a
time), attention in blocks of query rows, and the experts by a plain
loop over the held ones, each on the tokens that chose it. The parameter
tree is the program's (models/mimo.py:init_params), because the weights
are; the code is this file's own.

The same pass can be made in the precision "below": every product's
operands AND the residual sums, the norms, the scores, the softmax
weights and the router's scores rounded to bfloat16, where the
configuration's `assumed.precision` states float32 for the latter.
`below()` puts that control through `check`; `correct` never runs it.
"""

import time

import numpy as np

PROMPT_LENGTHS = (64, 127, 128, 129, 640, 1097, 2047, 2048)  # the sample
GENERATED = (0, 4, 7)   # rows held to the reference step by step: one
#                         prompt inside the window, one five windows long,
#                         one at the cap (the full caches' last rows)
QUERY_BLOCK = 512


def _keep(x):
    return x


def _rounding(precision: str):
    """What a pass does to every value it keeps (each product, norm,
    score, softmax weight, router score and residual sum): nothing in
    "float32", a rounding to bfloat16 in "below"."""
    import jax.numpy as jnp

    if precision == "float32":
        return _keep
    assert precision == "below", precision
    return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)


def _f32(leaf):
    import jax.numpy as jnp
    import ml_dtypes

    leaf = np.asarray(leaf)
    if leaf.dtype == np.dtype("V2"):      # npz keeps bfloat16 as raw pairs
        leaf = leaf.view(ml_dtypes.bfloat16)
    return jnp.asarray(leaf, jnp.float32)


def _float32(tree):
    import jax

    return jax.tree_util.tree_map(_f32, tree)


def _rms(scale, x, eps, to=_keep):
    import jax
    import jax.numpy as jnp

    return to(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
              * scale)


def _rotary(x, theta: float, rot: int):
    """x (S, H, D): position p turns dims (i, i + rot/2) by p theta^(-2i/rot)."""
    import jax.numpy as jnp

    half = rot // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rot)
    angle = jnp.asarray(np.arange(x.shape[0])[:, None] * freq[None, :],
                        jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle),
                            x[..., rot:]], axis=-1)


def _attention(config: dict, index: int, p: dict, x, to=_keep):
    import jax.numpy as jnp

    heads, dk = config["num_attention_heads"], config["head_dim"]
    dv = config["v_head_dim"]
    windowed = bool(config["hybrid_layer_pattern"][index])
    kv = (config["swa_num_key_value_heads"] if windowed
          else config["num_key_value_heads"])
    s = x.shape[0]
    fused = to(x @ p["qkv"]["kernel"])
    q = fused[:, :heads * dk].reshape(s, heads, dk)
    k = fused[:, heads * dk:(heads + kv) * dk].reshape(s, kv, dk)
    v = to(fused[:, (heads + kv) * dk:].reshape(s, kv, dv)
           * config["attention_value_scale"])
    theta = config["swa_rope_theta"] if windowed else config["rope_theta"]
    rot = int(dk * config["partial_rotary_factor"]) // 2 * 2
    q, k = to(_rotary(q, theta, rot)), to(_rotary(k, theta, rot))
    k = jnp.repeat(k, heads // kv, axis=1)      # head h reads h // group
    v = jnp.repeat(v, heads // kv, axis=1)
    sink = (p["sink"] if windowed
            and config["add_swa_attention_sink_bias"] else None)
    j = np.arange(s)[None, :]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        i = np.arange(lo, min(lo + QUERY_BLOCK, s))[:, None]
        seen = j <= i
        if windowed:
            seen &= i - j < config["sliding_window"]
        scores = to(jnp.einsum("qhd,khd->hqk", q[lo:lo + QUERY_BLOCK], k)
                    / np.sqrt(dk))
        scores = jnp.where(jnp.asarray(seen)[None], scores, -jnp.inf)
        if sink is not None:                     # one more column, no value
            scores = jnp.concatenate(
                [scores, jnp.broadcast_to(sink[:, None, None],
                                          (heads, scores.shape[1], 1))], -1)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        weights = to(weights / jnp.sum(weights, -1, keepdims=True))
        out.append(to(jnp.einsum("hqk,khd->qhd", weights[..., :s], v)))
    return to(jnp.concatenate(out).reshape(s, heads * dv)
              @ p["out"]["kernel"])


def _swiglu(x, w_in, w_out, to=_keep):
    import jax

    hidden = to(x @ w_in)
    width = w_out.shape[0]
    return to(to(jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ w_out)


def _experts(config: dict, p: dict, x, to=_keep):
    """The held experts' part of the layer for x (S, D)."""
    import jax
    import jax.numpy as jnp

    top_k = config["num_experts_per_tok"]
    offset = config["deployment"]["expert_offset"]
    scores = to(jax.nn.sigmoid(to(x @ p["router"])))
    _, chosen = jax.lax.top_k(scores + p["bias"], top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    y = np.zeros(x.shape, np.float32)
    for held in range(config["n_routed_experts"]):
        token, choice = np.nonzero(chosen == offset + held)
        if token.size:
            part = _swiglu(x[token], p["w_in"][held], p["w_out"][held], to)
            y[token] += np.asarray(part) * weights[token, choice][:, None]
    return jnp.asarray(y)


def _layer(config: dict, index: int, layer: dict, h, to=_keep):
    eps = config["layernorm_epsilon"]
    x = to(h + _attention(config, index, layer["attn"],
                          _rms(layer["attn_norm"]["scale"], h, eps, to), to))
    normed = _rms(layer["ffn_norm"]["scale"], x, eps, to)
    if config["moe_layer_freq"][index]:
        return to(x + _experts(config, layer["moe"], normed, to))
    return to(x + _swiglu(normed, layer["mlp"]["wi"]["kernel"],
                          layer["mlp"]["wo"]["kernel"], to))


def forward(tree: dict, config: dict, sequences, rows,
            precision: str = "float32") -> list:
    """For each sequence of `sequences` (each (S,) ids, each ONE forward
    pass of its own) the float32 logits (len(rows[k]), vocabulary slice)
    at its positions `rows[k]`. The layers are the outer loop, so a
    layer's weights are made float32 once."""
    import jax

    to = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        table = _f32(tree["embed"]["embedding"])
        hs = [table[np.asarray(ids)] for ids in sequences]
        del table
        for index in range(config["layers"]):
            layer = _float32(tree["layers"][index])
            hs = [_layer(config, index, layer, h, to) for h in hs]
        scale, head = _f32(tree["final_norm"]["scale"]), \
            _f32(tree["head"]["kernel"])
        return [np.asarray(_rms(scale, h[np.asarray(at)],
                                config["layernorm_epsilon"], to) @ head)
                for h, at in zip(hs, rows)]


def make_expected(params, config: dict, rng) -> dict:
    """The fixed prompts and the reference's logits at each one's last
    position (export child, on the CPU)."""
    width = config["serve"]["signature_kwargs"]["seq_len"]
    lengths = np.asarray([min(n, width) for n in PROMPT_LENGTHS], np.int32)
    prompts = np.zeros((len(lengths), width), np.int32)
    for row, n in enumerate(lengths):
        prompts[row, :n] = rng.integers(2, config["vocab_size"], (n,))
    first = forward(params, config,
                    [prompts[row, :n] for row, n in enumerate(lengths)],
                    [[n - 1] for n in lengths])
    return {"prompts": prompts, "lengths": lengths,
            "first_logits": np.concatenate(first)}


def _distances(got, want, bar: dict, name: str) -> tuple[dict, bool]:
    """Rows of logits against the reference's, by two numbers. The
    largest absolute difference of any row, under `logits_atol`: a fault
    of structure moves single logits by tenths. And the root mean square
    difference of a row, its median over the rows, under
    `logits_rms_atol`: the level of the rounding noise, which a residual
    stream, norm, softmax or router kept in bfloat16 about doubles while
    no single logit moves far (the median, because one flipped router
    choice lifts one row's level and says nothing of the precision)."""
    delta = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    worst = np.max(np.abs(delta), axis=-1)
    level = np.sqrt(np.mean(delta * delta, axis=-1))
    found = {f"{name}_max_abs_diff": float(np.max(worst)),
             f"{name}_diff_by_row": [float(d) for d in worst],
             f"{name}_atol": bar["logits_atol"],
             f"{name}_rms_diff": float(np.median(level)),
             f"{name}_rms_diff_by_row": [float(d) for d in level],
             f"{name}_rms_atol": bar["logits_rms_atol"]}
    return found, bool(np.isfinite(delta).all()
                       and np.max(worst) <= bar["logits_atol"]
                       and np.median(level) <= bar["logits_rms_atol"])


def check(ctx) -> dict:
    """Before the window (the benchmark's parent, numpy only): the fixed
    prompts through `serving_default`, the one program the cell times, at
    the one batch size it serves; `first_logits`, what the prefill chose
    each first token from, against the reference's logits at the last
    prompt position (`_distances`). The same request compiles or loads
    the program. The served generations of three prompts go to
    `verify`."""
    out: dict = {"ok": True, "seconds": {}}
    clock = time.monotonic()
    got = ctx.predict("serving_default",
                      {"input_ids": ctx.expected["prompts"]})
    out["seconds"]["whole_generation"] = time.monotonic() - clock
    found, out["ok"] = _distances(
        got["first_logits"], ctx.expected["first_logits"],
        ctx.config["correctness"], "first_logits")
    out.update(found)
    out["first_tokens_equal"] = float(np.mean(
        got["output_ids"][:, 0]
        == np.argmax(ctx.expected["first_logits"], -1)))
    rows = list(GENERATED)
    ctx.deferred["output_ids"] = got["output_ids"][rows]
    ctx.deferred["last_logits"] = got["last_logits"][rows]
    ctx.deferred["rows"] = np.asarray(rows, np.int32)
    return out


def verify(weights, config: dict, expected: dict, deferred: dict) -> dict:
    """After the window (a CPU child): prefill and then decoding through
    both caches against a full forward pass. For each deferred row the
    reference runs ONCE over prompt + served tokens, so position L - 1 + t
    gives the logits served token t was chosen from: the served token
    should be their argmax (counted up to the first end-of-sequence
    token; a near-tie may flip on rounding, so three quarters must agree
    and a differing token must lie within `generated_logit_gap` of the
    largest logit), and `last_logits`, what the program chose its last
    token from after all its decode steps, is held to the reference's at
    that position by the two tolerances of `first_logits`."""
    bar = config["correctness"]
    tree = {name: weights(name)
            for name in ("embed", "layers", "final_norm", "head")}
    served = np.asarray(deferred["output_ids"], np.int32)
    steps = served.shape[1]
    lengths = [int(expected["lengths"][row]) for row in deferred["rows"]]
    found = forward(
        tree, config,
        [np.concatenate([expected["prompts"][row, :n], served[k, :-1]])
         for k, (row, n) in enumerate(zip(deferred["rows"], lengths))],
        [np.arange(n - 1, n - 1 + steps) for n in lengths])
    equal, gaps = [], []
    for k, logits in enumerate(found):
        ended = np.flatnonzero(served[k] == config["eos_token_id"])
        counted = int(ended[0]) + 1 if ended.size else steps
        took = logits[np.arange(counted), served[k, :counted]]
        equal.append(np.argmax(logits[:counted], -1) == served[k, :counted])
        gaps.append(np.max(logits[:counted], -1) - took)
    equal, gaps = np.concatenate(equal), np.concatenate(gaps)
    share, gap = float(np.mean(equal)), float(np.max(gaps))
    last, near = _distances(deferred["last_logits"],
                            np.stack([logits[-1] for logits in found]),
                            bar, "last_logits")
    return {"ok": bool(share >= bar["min_equal_generated_tokens"]
                       and gap <= bar["generated_logit_gap"] and near),
            "generated_tokens_equal": share,
            "generated_tokens_compared": int(equal.size),
            "generated_logit_gap_max": gap,
            "generated_logit_gap": bar["generated_logit_gap"], **last}


def below(params, config: dict, expected: dict) -> dict:
    """The control of `logits_rms_atol`: what `check` says of a program
    that is right in everything but keeps the residual stream, the
    norms, the softmax and the router's scores in bfloat16, where the
    configuration states float32. The program stood in for is this
    file's own pass in the precision "below"; it has to come out as not
    correct."""
    import types

    lengths = expected["lengths"]
    logits = np.concatenate(forward(
        params, config,
        [expected["prompts"][row, :n] for row, n in enumerate(lengths)],
        [[n - 1] for n in lengths], "below"))
    steps = config["serve"]["signature_kwargs"]["max_decode_len"]
    answer = {"first_logits": logits, "last_logits": logits,
              "output_ids": np.repeat(np.argmax(logits, -1)[:, None], steps,
                                      axis=1).astype(np.int32)}
    return check(types.SimpleNamespace(
        config=config, expected=expected, deferred={},
        predict=lambda name, inputs: answer))


if __name__ == "__main__":
    # The control at the configuration's own size, on the CPU:
    #   python perfbench/configs/mimo-v2.5.reference.py <export dir>
    # with the directory a run of the cell left (.perfbench/models/
    # mimo-v2.5-w1: the served weights and the float32 logits); exits 0
    # when `check` calls the control not correct.
    import json
    import pathlib
    import sys

    here = pathlib.Path(__file__).resolve()
    sys.path.insert(0, str(here.parents[2]))
    from min_tfs_client_tpu.models import export

    config = json.loads(here.with_name("mimo-v2.5.json").read_text())
    made = pathlib.Path(sys.argv[1])
    stored = np.load(made / config["serve"]["model_name"] / "1"
                     / "params.npz", allow_pickle=False)
    found = below(export.unflatten_params({k: stored[k]
                                           for k in stored.files}),
                  config, dict(np.load(made / "expected.npz")))
    print(json.dumps(found))
    sys.exit(0 if not found["ok"] else 1)
