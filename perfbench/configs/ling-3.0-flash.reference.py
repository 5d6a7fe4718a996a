"""Ling-3.0-flash, one chip's share: the plain reference and the
comparison that decides `correct` for its cell.

The reference is the model's equations (ISSUE 51 states them; KDA after
arXiv:2510.26692 and flash-linear-attention's `fla/ops/kda/naive.py`,
MLA after DeepSeek-V2 with no low rank on the query, the router after
DeepSeek-V3's `noaux_tc`) in straightforward jax.numpy, float32 under
"highest" matmul precision: ONE full forward pass over one whole
sequence, with no cache, no kernel, no batching, no chunking and no
absorbed form. h0 = E[ids]; a layer is x = h + Mixer(RMSNorm(h)), h' = x
+ FFN(RMSNorm(x)); logits = RMSNorm(h) W_head.

KDA mixer (32 heads, d_k = d_v = 128): [q | k | v | a] = u W; q, k, v
through a causal depthwise convolution over 4 rows (no bias), then SiLU;
per head q = q / |q| * 128^-1/2, k = k / |k|; g = -5 sigmoid(exp(A_log)
(a + dt_bias)), one a key channel; beta = sigmoid(u W_beta), one a head;
per head S' = Diag(exp(g_t)) S_{t-1}, S_t = S' + beta_t k_t (v_t - S'^T
k_t)^T, o_t = S_t^T q_t, THE RECURRENCE RUN TOKEN BY TOKEN AS DEFINED (a
lax.scan over time: the chunked algorithm is what is under test); y =
[RMSNorm_head(o) * sigmoid(u W_g)_head] W_o. MLA mixer: q = u W_q (32 x
(128 | 64)); [c | k_r] = u W_kva (512 | 64); c = RMSNorm(c); [k_nope |
v]_h = c W_kvb; interleaved rotary (theta 6e6) on q's 64 rope lanes and
on the one k_r all heads share; score = (q_nope . k_nope + q_rope . k_r)
192^-1/2, causal softmax; y = [o_h * sigmoid(u W_g)_h] W_o. Feed-forward:
a dense SwiGLU of width 6,144 in the leading layer; after it s =
sigmoid(u W_r) over all 512 experts, the choice on s + bias: 8 groups of
64, a group's score the sum of its two largest, the 4 best groups stay,
the 8 best experts among them; w = s_e / sum of the chosen s, times 2.5;
y = sum w_e SwiGLU_e(u) over the experts THIS CHIP HOLDS (the same share
the program is given: `num_experts` experts from
`deployment.expert_offset`; what the absent experts would add is left out
here as there) + SwiGLU_shared(u), counted once. Logits and argmax are
over the vocabulary's slice.

It is computed layer by layer (one layer's weights in float32 at a
time), attention in blocks of query rows, the experts by a plain loop
over the held ones. The parameter tree is the program's
(models/ling_hybrid.py:init_params), because the weights are; the code is
this file's own.

The same pass can be made as a CONTROL, which `correct` never runs:
`precision="below"` rounds every product AND the residual sums, the
norms, g, exp(g), beta, the delta-rule state at every token, the scores,
the softmax weights and the router's scores to bfloat16, where the
configuration's `assumed.precision` states float32 for the latter; a
`fault` leaves one piece of the structure out (FAULTS). `control()` puts
either through `check`; each has to come out as not correct.
"""

import time

import numpy as np

PROMPT_LENGTHS = (1, 3, 4, 63, 64, 65, 512, 2047, 2048)  # the sample:
#   under, at and over the convolution's width and a chunk's edge, the cap
GENERATED = (1, 6, 8)   # rows held to the reference step by step: a prompt
#                         shorter than the convolution's window, a middle
#                         one, one at the cap (the latent cache's last rows)
QUERY_BLOCK = 512
# Faults of structure a plain forward pass can make (the hand-over's, the
# state taken after the padding and the window's or the latent cache's
# last row dropped, are made in the program itself:
# tests/unit/test_ling_hybrid.py).
FAULTS = ("decay_left_out", "beta_one", "rope_score_dropped",
          "group_limit_dropped", "expert_left_out", "shared_expert_left_out",
          "head_gate_left_out")


def _keep(x):
    return x


def _below(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rounding(precision: str):
    """What a pass does to every value it keeps: nothing in "float32", a
    rounding to bfloat16 in "below"."""
    if precision == "float32":
        return _keep
    assert precision == "below", precision
    return _below


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


def _rotate(x, theta: float):
    """Interleaved rotary over x's last dim (pairs 2i, 2i + 1) at
    positions 0..S-1; x (S, ..., R)."""
    import jax.numpy as jnp

    s, r = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(r // 2, dtype=np.float32) / (r // 2))
    angle = (np.arange(s, dtype=np.float32)[:, None] * inv).reshape(
        s, *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


_DELTA_RULE: dict = {}


def _delta_rule(to):
    """The recurrence, token by token, for one sequence: decay, q and k
    (S, H, d_k), beta (S, H), v (S, H, d_v) -> o (S, H, d_v). Compiled
    once a rounding and a shape (every layer and every control runs the
    same sequences)."""
    import jax
    import jax.numpy as jnp

    def run(decay, beta, q, k, v):
        def one(state, at):
            decay_t, beta_t, q_t, k_t, v_t = at
            decayed = decay_t[:, :, None] * state              # (H, dk, dv)
            seen = jnp.einsum("hkv,hk->hv", decayed, k_t)
            state = to(decayed + k_t[:, :, None]
                       * (beta_t[:, None] * (v_t - seen))[:, None, :])
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        _, o = jax.lax.scan(
            one, jnp.zeros((q.shape[1], q.shape[2], v.shape[2])),
            (decay, beta, q, k, v))
        return o

    if to not in _DELTA_RULE:
        _DELTA_RULE[to] = jax.jit(run)
    return _DELTA_RULE[to]


def _kda(config: dict, p: dict, u, to=_keep, fault=None):
    """u (S, D) normed -> the mixer's output (S, D)."""
    import jax
    import jax.numpy as jnp

    heads, d = config["num_attention_heads"], config["head_dim"]
    taps = config["short_conv_kernel_size"]
    hk = heads * d
    s = u.shape[0]
    proj = to(u @ p["qkvf"]["kernel"])
    before = jnp.concatenate([jnp.zeros((taps - 1, 3 * hk)),
                              proj[:, :3 * hk]])
    mixed = to(jax.nn.silu(sum(before[k:k + s] * p["conv"][k]
                               for k in range(taps))))
    q, k, v = (mixed[:, i * hk:(i + 1) * hk].reshape(s, heads, d)
               for i in range(3))
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q, k = to(unit(q) * d ** -0.5), to(unit(k))
    rate = jnp.repeat(jnp.exp(p["a_log"]), d)
    g = to(config["kda_lower_bound"] * jax.nn.sigmoid(
        rate * (proj[:, 3 * hk:] + p["dt_bias"])))
    decay = to(jnp.exp(g)).reshape(s, heads, d)
    gates = to(jax.nn.sigmoid(u @ p["bg"]["kernel"]))
    beta, gate = gates[:, :heads], gates[:, heads:]
    if fault == "decay_left_out":
        decay = jnp.ones_like(decay)
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    if fault == "head_gate_left_out":
        gate = jnp.ones_like(gate)

    o = _delta_rule(to)(decay, beta, q, k, v)
    o = _rms(p["norm"]["scale"], to(o), config["rms_norm_eps"], to)
    return to(to(o * gate[:, :, None]).reshape(s, hk) @ p["out"]["kernel"])


def _mla(config: dict, p: dict, u, to=_keep, fault=None):
    import jax
    import jax.numpy as jnp

    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    theta = float(config["rope_theta"])
    s = u.shape[0]
    q = to(u @ p["q"]["kernel"]).reshape(s, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], to(_rotate(q[..., nope:], theta))
    kva = to(u @ p["kva"]["kernel"])
    c = _rms(p["kv_norm"]["scale"], kva[:, :rank], config["rms_norm_eps"], to)
    k_rope = to(_rotate(kva[:, rank:], theta))                 # (S, rope)
    kv = to(c @ p["kvb"]["kernel"]).reshape(s, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    gate = to(jax.nn.sigmoid(u @ p["g"]["kernel"]))
    if fault == "head_gate_left_out":
        gate = jnp.ones_like(gate)
    j = np.arange(s)[None, :]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        i = np.arange(lo, min(lo + QUERY_BLOCK, s))[:, None]
        scores = jnp.einsum("qhd,khd->hqk", q_nope[lo:lo + QUERY_BLOCK],
                            k_nope)
        if fault != "rope_score_dropped":
            scores = scores + jnp.einsum(
                "qhd,kd->hqk", q_rope[lo:lo + QUERY_BLOCK], k_rope)
        scores = to(scores * (nope + rope) ** -0.5)
        scores = jnp.where(jnp.asarray(j <= i)[None], scores, -jnp.inf)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        weights = to(weights / jnp.sum(weights, -1, keepdims=True))
        out.append(to(jnp.einsum("hqk,khd->qhd", weights, v)))
    o = jnp.concatenate(out) * gate[:, :, None]
    return to(to(o).reshape(s, heads * dv) @ p["out"]["kernel"])


def _swiglu(x, w_in, w_out, to=_keep):
    import jax

    hidden = to(x @ w_in)
    width = w_out.shape[0]
    return to(to(jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ w_out)


def route(config: dict, p: dict, x, to=_keep, fault=None):
    """x (S, D) -> (chosen (S, k) expert ids over ALL experts, weights (S,
    k) float32 with the scaling factor), numpy."""
    import jax
    import jax.numpy as jnp

    top_k, groups = config["num_experts_per_tok"], config["n_group"]
    scores = to(jax.nn.sigmoid(x @ p["router"]))
    biased = scores + p["bias"]
    if fault != "group_limit_dropped":
        s, e = biased.shape
        by_group = biased.reshape(s, groups, e // groups)
        two, _ = jax.lax.top_k(by_group, 2)
        _, best = jax.lax.top_k(jnp.sum(two, -1), config["topk_group"])
        stays = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
        biased = jnp.where(stays[:, :, None], by_group,
                           -jnp.inf).reshape(s, e)
    _, chosen = jax.lax.top_k(biased, top_k)
    took = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = to(took / jnp.sum(took, -1, keepdims=True)
                 * config["routed_scaling_factor"])
    return np.asarray(chosen), np.asarray(weights)


def _experts(config: dict, p: dict, x, to=_keep, fault=None):
    """The held experts' part of the layer for x (S, D)."""
    import jax.numpy as jnp

    offset = config["deployment"]["expert_offset"]
    chosen, weights = route(config, p, x, to, fault)
    y = np.zeros(x.shape, np.float32)
    for held in range(config["num_experts"]):
        if fault == "expert_left_out" and held == 1:
            continue
        token, choice = np.nonzero(chosen == offset + held)
        if token.size:
            part = _swiglu(x[token], p["w_in"][held], p["w_out"][held], to)
            y[token] += np.asarray(part) * weights[token, choice][:, None]
    return jnp.asarray(y)


def _layer(config: dict, index: int, layer: dict, h, to=_keep, fault=None):
    eps = config["rms_norm_eps"]
    u = _rms(layer["norm"]["scale"], h, eps, to)
    if config["layer_types"][index] == "kda":
        mixed = _kda(config, layer["kda"], u, to, fault)
    else:
        mixed = _mla(config, layer["mla"], u, to, fault)
    x = to(h + mixed)
    u = _rms(layer["ffn_norm"]["scale"], x, eps, to)
    if config["ffn_types"][index] == "dense":
        return to(x + _swiglu(u, layer["mlp"]["wi"]["kernel"],
                              layer["mlp"]["wo"]["kernel"], to))
    ffn = _experts(config, layer["moe"], u, to, fault)
    if fault != "shared_expert_left_out":
        ffn = ffn + _swiglu(u, layer["shared"]["w_in"],
                            layer["shared"]["w_out"], to)
    return to(x + ffn)


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
        hs = [to(table[np.asarray(ids)]) for ids in sequences]
        del table
        for index in range(config["layers"]):
            layer = _float32(tree["layers"][index])
            hs = [_layer(config, index, layer, h, to, fault) for h in hs]
            del layer
        scale = _f32(tree["final_norm"]["scale"])
        head = _f32(tree["head"]["kernel"])
        return [np.asarray(to(_rms(scale, h[np.asarray(at)],
                                   config["rms_norm_eps"], to) @ head))
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
    the state, the window and the latent cache against a full forward
    pass. For each deferred row the reference runs ONCE over prompt +
    served tokens, so position L - 1 + t gives the logits served token t
    was chosen from: the served token should be their argmax (counted up
    to the first end-of-sequence token; a near-tie may flip on rounding,
    so three quarters must agree and a differing token must lie within
    `generated_logit_gap` of the largest logit), and `last_logits`, what
    the program chose its last token from after all its decode steps, is
    held to the reference's at that position by the two tolerances of
    `first_logits`."""
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
    #   python perfbench/configs/ling-3.0-flash.reference.py <export dir>
    # with the directory a run of the cell left (.perfbench/models/
    # ling-3.0-flash-w1: the served weights and the float32 logits);
    # prints what `check` says of each control, a line each, and exits 0
    # when every one comes out as not correct.
    import json
    import pathlib
    import sys

    here = pathlib.Path(__file__).resolve()
    sys.path.insert(0, str(here.parents[2]))
    from min_tfs_client_tpu.models import export

    config = json.loads(here.with_name("ling-3.0-flash.json").read_text())
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
