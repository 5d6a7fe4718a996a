"""Granite-4.0-H-Small, one chip's share: the plain reference and the
comparison that decides `correct` for its cell.

The reference is the published model's equations (transformers'
GraniteMoeHybrid) in straightforward jax.numpy, float32 under "highest"
matmul precision: ONE full forward pass over one whole sequence, with no
cache, no kernel, no batching and no chunking. x0 = 12 E[ids]; a layer is
x = h + 0.22 Mixer(RMSNorm(h)), h' = x + 0.22 (Experts(RMSNorm(x)) +
Shared(RMSNorm(x))); logits = RMSNorm(h) E^T / 16 (the head is the
embedding). Mamba-2 mixer (128 heads x 64, one group, state 128): [z |
xBC | dt] = u W_in; xBC = silu(causal conv over 4 rows + bias); [x | B |
C] = xBC; dt = softplus(dt + dt_bias), A = -exp(A_log); per head H_t =
exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t, y_t = H_t C_t + D x_t, THE
RECURRENCE RUN TOKEN BY TOKEN AS DEFINED (a lax.scan over time: the
chunked algorithm is what is under test); y = RMSNorm(y * silu(z)) over
all 8,192; out = y W_out. Attention mixer: q 32 x 128, k and v 8 x 128,
no bias, no positions, causal, scores q.k / 128; query head h reads K/V
head h // 4. Experts: logits = u W_r over all 72, the top 10, w =
softmax of those ten logits; y = sum w_e SwiGLU_e(u) over the experts
THIS CHIP HOLDS (the same share the program is given: `num_local_experts`
experts from `deployment.expert_offset`; what the absent experts would
add is left out here as there). Shared: the same gated form at width
1,536, every token, counted once. Logits and argmax are over the
vocabulary's slice.

It is computed layer by layer (one layer's weights in float32 at a
time), attention in blocks of query rows, the experts by a plain loop
over the held ones. The parameter tree is the program's
(models/granite_hybrid.py:init_params), because the weights are; the
code is this file's own.

The same pass can be made as a CONTROL, which `correct` never runs:
`precision="below"` rounds every product AND the residual sums, the
norms, dt, the decays, the recurrent state at every token, the scores,
the softmax weights and the router's logits to bfloat16, where the
configuration's `assumed.precision` states float32 for the latter; a
`fault` leaves one piece of the structure out (FAULTS). `control()` puts
either through `check`; each has to come out as not correct.
"""

import time

import numpy as np

PROMPT_LENGTHS = (1, 3, 4, 255, 256, 257, 640, 2047, 2048)  # the sample:
#   under, at and over the convolution's width and a chunk's edge, the cap
GENERATED = (1, 6, 8)   # rows held to the reference step by step: a prompt
#                         shorter than the convolution's window, a middle
#                         one, one at the cap (the cache's last rows)
QUERY_BLOCK = 512
# Faults of structure a plain forward pass can make (the two of the
# hand-over to decoding, the state taken after the padding and the
# window's last row dropped, are made in the program itself:
# tests/perfbench/test_granite_reference.py).
FAULTS = ("dx_left_out", "residual_multiplier_one", "expert_left_out",
          "shared_expert_left_out")


def _keep(x):
    return x


def _rounding(precision: str):
    """What a pass does to every value it keeps: nothing in "float32", a
    rounding to bfloat16 in "below"."""
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


def _mamba(config: dict, p: dict, u, to=_keep, fault=None):
    """u (S, D) normed -> the mixer's output (S, D)."""
    import jax
    import jax.numpy as jnp

    heads, hd = config["mamba_n_heads"], config["mamba_d_head"]
    n, taps = config["mamba_d_state"], config["mamba_d_conv"]
    di = heads * hd
    s = u.shape[0]
    proj = to(u @ p["in"]["kernel"])
    z, xbc, dt = proj[:, :di], proj[:, di:di + di + 2 * n], \
        proj[:, di + di + 2 * n:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = to(jax.nn.silu(sum(padded[k:k + s] * p["conv"][k]
                             for k in range(taps)) + p["conv_bias"]))
    x = xbc[:, :di].reshape(s, heads, hd)
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    dt = to(jax.nn.softplus(dt + p["dt_bias"]))                # (S, H)
    decay = to(jnp.exp(dt * -jnp.exp(p["a_log"])))

    def one(h, at):
        decay_t, dt_t, x_t, b_t, c_t = at
        h = to(decay_t[:, None, None] * h
               + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, h @ c_t                                      # (H, P)

    _, y = jax.lax.scan(one, jnp.zeros((heads, hd, n)),
                        (decay, dt, x, b, c))
    if fault != "dx_left_out":
        y = y + p["d"][None, :, None] * x
    y = to(y).reshape(s, di)
    gated = to(y * jax.nn.silu(z))
    return to(_rms(p["norm"]["scale"], gated, config["rms_norm_eps"], to)
              @ p["out"]["kernel"])


def _attention(config: dict, p: dict, u, to=_keep):
    import jax.numpy as jnp

    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // heads
    s = u.shape[0]
    fused = to(u @ p["qkv"]["kernel"])
    q = fused[:, :heads * hd].reshape(s, heads, hd)
    k = fused[:, heads * hd:(heads + kv) * hd].reshape(s, kv, hd)
    v = fused[:, (heads + kv) * hd:].reshape(s, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=1)      # head h reads h // group
    v = jnp.repeat(v, heads // kv, axis=1)
    j = np.arange(s)[None, :]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        i = np.arange(lo, min(lo + QUERY_BLOCK, s))[:, None]
        scores = to(jnp.einsum("qhd,khd->hqk", q[lo:lo + QUERY_BLOCK], k)
                    * config["attention_multiplier"])
        scores = jnp.where(jnp.asarray(j <= i)[None], scores, -jnp.inf)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        weights = to(weights / jnp.sum(weights, -1, keepdims=True))
        out.append(to(jnp.einsum("hqk,khd->qhd", weights, v)))
    return to(jnp.concatenate(out).reshape(s, heads * hd)
              @ p["out"]["kernel"])


def _swiglu(x, w_in, w_out, to=_keep):
    import jax

    hidden = to(x @ w_in)
    width = w_out.shape[0]
    return to(to(jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ w_out)


def _experts(config: dict, p: dict, x, to=_keep, fault=None):
    """The held experts' part of the layer for x (S, D)."""
    import jax
    import jax.numpy as jnp

    offset = config["deployment"]["expert_offset"]
    logits = to(x @ p["router"])
    chosen_logits, chosen = jax.lax.top_k(logits,
                                          config["num_experts_per_tok"])
    weights = np.asarray(to(jax.nn.softmax(chosen_logits, axis=-1)))
    chosen = np.asarray(chosen)
    y = np.zeros(x.shape, np.float32)
    for held in range(config["num_local_experts"]):
        if fault == "expert_left_out" and held == 1:
            continue
        token, choice = np.nonzero(chosen == offset + held)
        if token.size:
            part = _swiglu(x[token], p["w_in"][held], p["w_out"][held], to)
            y[token] += np.asarray(part) * weights[token, choice][:, None]
    return jnp.asarray(y)


def _layer(config: dict, index: int, layer: dict, h, to=_keep, fault=None):
    eps = config["rms_norm_eps"]
    m = (1.0 if fault == "residual_multiplier_one"
         else config["residual_multiplier"])
    u = _rms(layer["norm"]["scale"], h, eps, to)
    if config["layer_types"][index] == "mamba":
        mixed = _mamba(config, layer["mamba"], u, to, fault)
    else:
        mixed = _attention(config, layer["attn"], u, to)
    x = to(h + m * mixed)
    u = _rms(layer["ffn_norm"]["scale"], x, eps, to)
    ffn = _experts(config, layer["moe"], u, to, fault)
    if fault != "shared_expert_left_out":
        ffn = ffn + _swiglu(u, layer["shared"]["w_in"],
                            layer["shared"]["w_out"], to)
    return to(x + m * ffn)


def forward(tree: dict, config: dict, sequences, rows,
            precision: str = "float32", fault=None) -> list:
    """For each sequence of `sequences` (each (S,) ids, each ONE forward
    pass of its own) the float32 logits (len(rows[k]), vocabulary slice)
    at its positions `rows[k]`. The layers are the outer loop, so a
    layer's weights are made float32 once."""
    import jax

    to = _rounding(precision)
    with jax.default_matmul_precision("highest"):
        table = _f32(tree["embed"]["embedding"])
        hs = [to(table[np.asarray(ids)] * config["embedding_multiplier"])
              for ids in sequences]
        for index in range(config["layers"]):
            layer = _float32(tree["layers"][index])
            hs = [_layer(config, index, layer, h, to, fault) for h in hs]
        scale = _f32(tree["final_norm"]["scale"])
        return [np.asarray(to(_rms(scale, h[np.asarray(at)],
                                   config["rms_norm_eps"], to) @ table.T
                              / config["logits_scaling"]))
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
    stream, norm, decay, state or router kept in bfloat16 lifts while no
    single logit moves far (the median, because one flipped router
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
    the state, the window and the cache against a full forward pass. For
    each deferred row the reference runs ONCE over prompt + served
    tokens, so position L - 1 + t gives the logits served token t was
    chosen from: the served token should be their argmax (counted up to
    the first end-of-sequence token; a near-tie may flip on rounding, so
    three quarters must agree and a differing token must lie within
    `generated_logit_gap` of the largest logit), and `last_logits`, what
    the program chose its last token from after all its decode steps, is
    held to the reference's at that position by the two tolerances of
    `first_logits`."""
    bar = config["correctness"]
    tree = {name: weights(name)
            for name in ("embed", "layers", "final_norm")}
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


def control(params, config: dict, expected: dict, precision: str = "float32",
            fault=None) -> dict:
    """What `check` says of a program that is right in everything but
    one: the precision "below" (bfloat16 where the configuration states
    float32), or one fault of FAULTS. The program stood in for is this
    file's own pass made so; it has to come out as not correct."""
    import types

    lengths = expected["lengths"]
    logits = np.concatenate(forward(
        params, config,
        [expected["prompts"][row, :n] for row, n in enumerate(lengths)],
        [[n - 1] for n in lengths], precision, fault))
    steps = config["serve"]["signature_kwargs"]["max_decode_len"]
    answer = {"first_logits": logits, "last_logits": logits,
              "output_ids": np.repeat(np.argmax(logits, -1)[:, None], steps,
                                      axis=1).astype(np.int32)}
    return check(types.SimpleNamespace(
        config=config, expected=expected, deferred={},
        predict=lambda name, inputs: answer))


if __name__ == "__main__":
    # The controls at the configuration's own size, on the CPU:
    #   python perfbench/configs/granite-4.0-h-small.reference.py <export dir>
    # with the directory a run of the cell left (.perfbench/models/
    # granite-4.0-h-small-w1: the served weights and the float32 logits);
    # prints what `check` says of each control, a line each, and exits 0
    # when every one comes out as not correct.
    import json
    import pathlib
    import sys

    here = pathlib.Path(__file__).resolve()
    sys.path.insert(0, str(here.parents[2]))
    from min_tfs_client_tpu.models import export

    config = json.loads(here.with_name("granite-4.0-h-small.json").read_text())
    made = pathlib.Path(sys.argv[1])
    stored = np.load(made / config["serve"]["model_name"] / "1"
                     / "params.npz", allow_pickle=False)
    params = export.unflatten_params({k: stored[k] for k in stored.files})
    expected = dict(np.load(made / "expected.npz"))
    passed = []
    for precision, fault in [("below", None)] + [("float32", f)
                                                 for f in FAULTS]:
        found = control(params, config, expected, precision, fault)
        found.pop("seconds")
        print(json.dumps({"control": fault or precision, **found}),
              flush=True)
        passed.append(found["ok"])
    sys.exit(0 if not any(passed) else 1)
