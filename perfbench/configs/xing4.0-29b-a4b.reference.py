"""Xing4.0-29B-A4B, one chip's share: the plain reference and the
comparison that decides `correct` for its cell.

The reference is the model's equations (ISSUE 54 states them; MLA with a
low-rank query after DeepSeek-V2/V3, YaRN as DeepSeek-V3's, the router
after `noaux_tc` with one group, the residual path after mHC,
arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606) in
straightforward jax.numpy, float32 under "highest" matmul precision: ONE
full forward pass over one whole sequence, with no cache, no kernel, no
packing, no chunking and no absorbed form.

Residual path: a token's stream is X (n = 4 streams x C), every stream
the token's embedding at the entry. Each sub-layer s (a layer's
attention, then its feed-forward) owns phi_s (nC x (n^2 + 2n)), three
scalars alpha_s and a bias b_s: r = rsqrt(mean(vec(X)^2) + hc_eps); m = r
(vec(X) phi_s); H_pre = sigmoid(alpha_pre m[0:n] + b[0:n]); H_post = 2
sigmoid(alpha_post m[n:2n] + b[n:2n]); A = clip(alpha_res mat(m[2n:]) +
mat(b[2n:]), -30, 30), M = exp(A), then 20 rounds of M <- M / (rowsum(M)
+ hc_eps), M <- M / (colsum(M) + hc_eps) AS A PLAIN LOOP, H_res = M; u =
sum_i H_pre[i] X[i]; y = F_s(RMSNorm_s(u)); X'[i] = sum_j H_res[i, j] X[j]
+ H_post[i] y. Exit: h = sum_i X[i], the final RMSNorm, the head.

MLA (32 heads): c_q = RMSNorm(u W_qa) (768); q = c_q W_qb (32 x (128 |
64)); [c | k_r] = u W_kva (512 | 64); c = RMSNorm(c); [k_nope | v]_h = c
W_kvb; interleaved rotary on q's 64 rope lanes and on the one k_r all
heads share, its inverse frequencies theta^(-2i/64) blended with the same
divided by 64 through the linear ramp between the correction dimensions
of beta_fast 32 and beta_slow 1 at the original 4,096 positions (YaRN);
score = (q_nope . k_nope + q_rope . k_r) 192^-1/2 (0.1 ln 64 + 1)^2,
causal softmax, explicit scores over decompressed K and V; y = o W_o. No
gate. Feed-forward: a dense SwiGLU of width 9,216 in the leading layer;
after it s = sigmoid(u W_r) over all 64 experts, the 4 largest of s +
bias chosen, w = s_e / sum of the chosen s, times 2; y = sum w_e
SwiGLU_e(u) over the experts THIS CHIP HOLDS (the same share the program
is given: `n_routed_experts` experts from `deployment.expert_offset`;
what the absent experts would add is left out here as there) +
SwiGLU_shared(u), counted once. Logits and argmax are over the
vocabulary's slice.

It is computed layer by layer (one layer's weights in float32 at a
time), attention in blocks of query rows, the experts by a plain loop
over the held ones. The parameter tree is the program's
(models/xing.py:init_params), because the weights are; the code is this
file's own.

The same pass can be made as a CONTROL, which `correct` never runs:
`precision="below"` rounds every product AND the streams, the norms, m,
the three maps, each of Sinkhorn's rounds, the scores, the softmax
weights and the router's scores to bfloat16, where the configuration's
`assumed.precision` states float32 for the latter; a `fault` leaves one
piece of the structure out (FAULTS). `control()` puts either through
`check`; each has to come out as not correct.
"""

import math
import time

import numpy as np

PROMPT_LENGTHS = (1, 2, 127, 128, 129, 512, 1024, 2047, 2048)  # the sample:
#   one token, both sides of the flash kernel's block of 128, the middle,
#   the cap
GENERATED = (1, 5, 8)   # rows held to the reference step by step: prompts
#                         of 2, 512 and 2,048 tokens (the cap: decoding
#                         writes the caches' last rows)
QUERY_BLOCK = 512
ROW_BLOCK = 64       # an expert's rows are taken in whole blocks of these
# Faults of structure a plain forward pass can make (the hand-over's, a
# latent cache's last row dropped in one layer, the step's rotary position
# off by one, a padded row that writes, are made in the program itself:
# tests/unit/test_xing.py).
FAULTS = ("sinkhorn_left_out", "post_factor_dropped", "pre_uniform",
          "exit_first_stream", "yarn_dropped", "query_norm_dropped",
          "rope_score_dropped", "expert_left_out", "shared_expert_left_out")


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


def inverse_frequencies(config: dict, fault=None) -> np.ndarray:
    """Of the 32 rotary pairs: theta^(-2i/64), under YaRN blended with the
    same divided by the factor."""
    half = config["qk_rope_head_dim"] // 2
    theta = float(config["rope_theta"])
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)
    yarn = config.get("rope_scaling")
    if not yarn or fault == "yarn_dropped":
        return plain.astype(np.float32)
    dim, original = 2 * half, yarn["original_max_position_embeddings"]

    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    return (plain / yarn["factor"] * ramp + plain * (1 - ramp)).astype(
        np.float32)


def attention_scale(config: dict, fault=None) -> float:
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    yarn = config.get("rope_scaling")
    if not yarn or fault == "yarn_dropped" or yarn["factor"] <= 1:
        return scale
    return scale * (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"])
                    + 1.0) ** 2


def _rotate(x, inv):
    """Interleaved rotary over x's last dim (pairs 2i, 2i + 1) at
    positions 0..S-1; x (S, ..., R)."""
    import jax.numpy as jnp

    s, r = x.shape[0], x.shape[-1]
    angle = (np.arange(s, dtype=np.float32)[:, None] * inv).reshape(
        s, *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _mla(config: dict, p: dict, u, to=_keep, fault=None):
    """u (S, D) normed -> the mixer's output (S, D)."""
    import jax.numpy as jnp

    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    eps = config["rms_norm_eps"]
    inv = inverse_frequencies(config, fault)
    s = u.shape[0]
    low = to(u @ p["qa"]["kernel"])
    if fault != "query_norm_dropped":
        low = _rms(p["q_norm"]["scale"], low, eps, to)
    q = to(low @ p["qb"]["kernel"]).reshape(s, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], to(_rotate(q[..., nope:], inv))
    kva = to(u @ p["kva"]["kernel"])
    c = _rms(p["kv_norm"]["scale"], kva[:, :rank], eps, to)
    k_rope = to(_rotate(kva[:, rank:], inv))                   # (S, rope)
    kv = to(c @ p["kvb"]["kernel"]).reshape(s, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    attend = _attend(to, attention_scale(config, fault),
                     fault != "rope_score_dropped")
    out = [attend(q_nope[lo:lo + QUERY_BLOCK], q_rope[lo:lo + QUERY_BLOCK],
                  k_nope, k_rope, v, lo)
           for lo in range(0, s, QUERY_BLOCK)]
    return to(jnp.concatenate(out).reshape(s, heads * dv)
              @ p["out"]["kernel"])


_ATTEND: dict = {}


def _attend(to, scale: float, with_rope: bool):
    """A block of query rows from position `lo` over ALL keys, as
    explicit scores: (q_nope . k_nope + q_rope . k_r) scale, the causal
    mask, softmax, the weighted sum of V -> (block, heads, d_v).
    Compiled once a rounding, a scale and a shape (every layer runs the
    same sequences); eager, the same operations take ten times as long
    on a CPU."""
    import jax
    import jax.numpy as jnp

    def run(q_nope, q_rope, k_nope, k_rope, v, lo):
        scores = jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
        if with_rope:
            scores = scores + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
        scores = to(scores * scale)
        i = lo + jnp.arange(q_nope.shape[0])[:, None]
        j = jnp.arange(k_nope.shape[0])[None, :]
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        weights = to(weights / jnp.sum(weights, -1, keepdims=True))
        return to(jnp.einsum("hqk,khd->qhd", weights, v))

    key = (to, scale, with_rope)
    if key not in _ATTEND:
        _ATTEND[key] = jax.jit(run)
    return _ATTEND[key]


def _swiglu(x, w_in, w_out, to=_keep):
    import jax

    hidden = to(x @ w_in)
    width = w_out.shape[0]
    return to(to(jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ w_out)


def route(config: dict, p: dict, x, to=_keep) -> tuple:
    """x (S, D) -> (chosen (S, k) expert ids over ALL experts, weights (S,
    k) float32 with the scaling factor), numpy."""
    import jax
    import jax.numpy as jnp

    scores = to(jax.nn.sigmoid(x @ p["router"]))
    _, chosen = jax.lax.top_k(scores + p["bias"],
                              config["num_experts_per_tok"])
    took = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = to(took / jnp.sum(took, -1, keepdims=True)
                 * config["routed_scaling_factor"])
    return np.asarray(chosen), np.asarray(weights)


def _experts(config: dict, p: dict, x, to=_keep, fault=None):
    """The held experts' part of the layer for x (S, D)."""
    import jax.numpy as jnp

    offset = config["deployment"]["expert_offset"]
    chosen, weights = route(config, p, x, to)
    y = np.zeros(x.shape, np.float32)
    for held in range(config["n_routed_experts"]):
        if fault == "expert_left_out" and held == 1:
            continue
        token, choice = np.nonzero(chosen == offset + held)
        if token.size:
            # its rows, padded to whole blocks (a shape met before is a
            # program compiled before; a padding row is row 0 again and
            # is dropped)
            rows = np.zeros(-(-token.size // ROW_BLOCK) * ROW_BLOCK, np.int64)
            rows[:token.size] = token
            part = _swiglu(x[rows], p["w_in"][held], p["w_out"][held], to)
            y[token] += (np.asarray(part)[:token.size]
                         * weights[token, choice][:, None])
    return jnp.asarray(y)


def _feed_forward(config: dict, layer: dict, u, to=_keep, fault=None):
    if "mlp" in layer:
        return _swiglu(u, layer["mlp"]["wi"]["kernel"],
                       layer["mlp"]["wo"]["kernel"], to)
    y = _experts(config, layer["moe"], u, to, fault)
    if fault != "shared_expert_left_out":
        y = y + _swiglu(u, layer["shared"]["w_in"], layer["shared"]["w_out"],
                        to)
    return y


def _sub_layer(config: dict, hc: dict, norm: dict, x, branch, to=_keep,
               fault=None):
    """One hyper-connected sub-layer: x (S, n, C) -> (S, n, C); `branch(u
    (S, C) normed) -> (S, C)`."""
    import jax
    import jax.numpy as jnp

    s, n, _ = x.shape
    eps = config["hc_eps"]
    flat = x.reshape(s, -1)
    r = jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    m = to((flat @ hc["phi"]) * r)
    alpha, bias = hc["alpha"], hc["bias"]
    pre = to(jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n]))
    post = jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + bias[n:2 * n])
    post = to(post if fault == "post_factor_dropped" else 2.0 * post)
    if fault == "pre_uniform":
        pre = jnp.full_like(pre, 1.0 / n)
    matrix = to(jnp.exp(jnp.clip(
        alpha[2] * m[:, 2 * n:] + bias[2 * n:],
        config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"])
    )).reshape(s, n, n)
    for _ in range(config["hc_sinkhorn_iters"]):
        matrix = to(matrix / (jnp.sum(matrix, -1, keepdims=True) + eps))
        if fault == "sinkhorn_left_out":
            break        # the row-normalised exp(A) alone
        matrix = to(matrix / (jnp.sum(matrix, -2, keepdims=True) + eps))
    u = to(jnp.einsum("sn,snc->sc", pre, x))
    y = branch(_rms(norm["scale"], u, config["rms_norm_eps"], to))
    return to(jnp.einsum("sij,sjc->sic", matrix, x)
              + post[:, :, None] * y[:, None, :])


def _layer(config: dict, layer: dict, x, to=_keep, fault=None):
    x = _sub_layer(config, layer["attn_hc"], layer["norm"], x,
                   lambda u: _mla(config, layer["mla"], u, to, fault),
                   to, fault)
    return _sub_layer(config, layer["ffn_hc"], layer["ffn_norm"], x,
                      lambda u: _feed_forward(config, layer, u, to, fault),
                      to, fault)


def forward(tree: dict, config: dict, sequences, rows,
            precision: str = "float32", fault=None) -> list:
    """For each sequence of `sequences` (each (S,) ids, each ONE forward
    pass of its own) the float32 logits (len(rows[k]), vocabulary slice)
    at its positions `rows[k]`. The layers are the outer loop, so a
    layer's weights are made float32 once."""
    import jax
    import jax.numpy as jnp

    to = _rounding(precision)
    n = config["hc_mult"]
    with jax.default_matmul_precision("highest"):
        table = _f32(tree["embed"]["embedding"])
        xs = [to(jnp.repeat(table[np.asarray(ids)][:, None], n, axis=1))
              for ids in sequences]
        del table
        for index in range(config["layers"]):
            layer = _float32(tree["layers"][index])
            xs = [_layer(config, layer, x, to, fault) for x in xs]
            del layer
        scale = _f32(tree["final_norm"]["scale"])
        head = _f32(tree["head"]["kernel"])
        leave = (lambda x: x[:, 0]) if fault == "exit_first_stream" \
            else (lambda x: jnp.sum(x, axis=1))
        return [np.asarray(to(_rms(scale, to(leave(x[np.asarray(at)])),
                                   config["rms_norm_eps"], to) @ head))
                for x, at in zip(xs, rows)]


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
    `logits_rms_atol`: the level of the rounding noise, which streams,
    norms, maps or a router kept in bfloat16 lift while no single logit
    moves far (the median, because one flipped router choice lifts one
    row's level and says nothing of the precision)."""
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
    the twenty latent caches against a full forward pass. For each
    deferred row the reference runs ONCE over prompt + served tokens, so
    position L - 1 + t gives the logits served token t was chosen from:
    the served token should be their argmax (counted up to the first
    end-of-sequence token; a near-tie may flip on rounding, so three
    quarters must agree and a differing token must lie within
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
    #   python perfbench/configs/xing4.0-29b-a4b.reference.py <export dir>
    # with the directory a run of the cell left (.perfbench/models/
    # xing4.0-29b-a4b-w1: the served weights and the float32 logits);
    # prints what `check` says of each control, a line each, and exits 0
    # when every one comes out as not correct.
    import json
    import pathlib
    import sys

    here = pathlib.Path(__file__).resolve()
    sys.path.insert(0, str(here.parents[2]))
    from min_tfs_client_tpu.models import export

    config = json.loads(here.with_name("xing4.0-29b-a4b.json").read_text())
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
