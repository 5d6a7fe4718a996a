"""T5-large: the plain reference and the comparison that decides
`correct` for its cells.

The reference is the published architecture in straightforward
jax.numpy, float32 under "highest" matmul precision, with no kernel, no
cache and no batching: the encoder (pre-RMS-norm blocks, unscaled
attention with the bucketed relative-position bias of the first layer
shared by all, ReLU MLP, no biases) and the decoder over a whole given
sequence at once (causal self-attention, then attention to the encoder's
output; logits from the tied embedding after the 1/sqrt(d_model)
rescale). Given only the start token that is the first step; given the
tokens the served model generated, as one whole generation or step by
step through a decode session, it is every step, each on the served
prefix (`verify`). The parameter tree is the program's
(models/t5.py:init_params), because the weights are. Written for this
directory, independent of models/t5.py's code.
"""

import concurrent.futures as cf

import numpy as np

import time

PROMPT_LENGTHS = (512, 300, 128, 64, 400, 256, 200, 96)  # the fixed sample
ENCODED = 2                            # prompts whose encodings are kept
SHORT_SESSIONS, SHORT_STEPS = 4, 40    # concurrent: they share ticks
LONG_STEPS = 136                       # alone: crosses into the widest table


def adjust_params(params, config: dict):
    """T5's own initialisation folds attention's 1/sqrt(d_kv) into the
    query projection (std (d_model * d_kv)^-0.5; keys and values
    d_model^-0.5), which is why its attention is unscaled. models/t5.py's
    init_params gives all three d_model^-0.5, so scores of 64-wide heads
    have a standard deviation near 8: every softmax is close to one-hot,
    and a bfloat16 rounding of a score changes which key wins. Through 24
    layers that makes the random-weight model chaotic, and no comparison
    with a float32 reference can tell a correct program from a wrong one
    (the first chip run read an encoder difference of 2.5 on values of
    unit scale). The benchmark therefore serves the published
    initialisation: the query kernels times d_kv^-0.5. Shapes, types and
    the work per token are unchanged."""
    scale = config["d_kv"] ** -0.5

    def fix(block):
        for name in ("self_attention", "cross_attention"):
            if name in block:
                q = block[name]["query"]
                q["kernel"] = q["kernel"] * scale
    for stack in ("encoder", "decoder"):
        for block in params[stack]["layers"]:
            fix(block)
    return params


def _bucket(rel, *, bidirectional: bool, num_buckets: int, max_distance: int):
    import jax.numpy as jnp

    bucket = 0
    if bidirectional:
        num_buckets //= 2
        bucket = jnp.where(rel > 0, num_buckets, 0)
        rel = jnp.abs(rel)
    else:
        rel = -jnp.minimum(rel, 0)
    max_exact = num_buckets // 2
    large = max_exact + (
        jnp.log(rel.astype(jnp.float32) / max_exact + 1e-9)
        / np.log(max_distance / max_exact) * (num_buckets - max_exact)
    ).astype(jnp.int32)
    return bucket + jnp.where(rel < max_exact, rel,
                              jnp.minimum(large, num_buckets - 1))


def _bias(table, qlen: int, klen: int, config: dict, *, bidirectional: bool):
    import jax.numpy as jnp

    rel = jnp.arange(klen)[None, :] - jnp.arange(qlen)[:, None]
    buckets = _bucket(
        rel, bidirectional=bidirectional,
        num_buckets=config["relative_attention_num_buckets"],
        max_distance=config["assumed"]["relative_attention_max_distance"])
    return table[buckets].transpose(2, 0, 1)[None]      # (1, H, q, k)


def _rms(p, x, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _attend(p, x, kv, heads: int, bias, mask):
    """`mask` is (B, q or 1, k): which keys a query row may see."""
    import jax
    import jax.numpy as jnp

    b, q_len, _ = x.shape
    k_len = kv.shape[1]
    q = (x @ p["query"]["kernel"]).reshape(b, q_len, heads, -1)
    k = (kv @ p["key"]["kernel"]).reshape(b, k_len, heads, -1)
    v = (kv @ p["value"]["kernel"]).reshape(b, k_len, heads, -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)        # T5: unscaled
    if bias is not None:
        scores = scores + bias
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(b, q_len, -1) @ p["out"]["kernel"]


def _mlp(p, x):
    import jax

    return jax.nn.relu(x @ p["wi"]["kernel"]) @ p["wo"]["kernel"]


def _float32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), tree)


def _encode(tree, config: dict, input_ids, key_mask):
    """Encoder output (B, S, d_model); `tree` holds float32 weights."""
    eps, heads = config["layer_norm_epsilon"], config["num_heads"]
    enc = tree["encoder"]
    s = input_ids.shape[1]
    bias = _bias(enc["rel_bias"]["embedding"], s, s, config,
                 bidirectional=True)
    x = tree["shared_embedding"]["embedding"][input_ids]
    for layer in enc["layers"]:
        h = _rms(layer["self_norm"], x, eps)
        x = x + _attend(layer["self_attention"], h, h, heads, bias,
                        key_mask[:, None])
        x = x + _mlp(layer["mlp"], _rms(layer["mlp_norm"], x, eps))
    return _rms(enc["final_norm"], x, eps)


def _decode(tree, config: dict, encoded, key_mask, decoder_ids):
    """Logits (B, T, vocab) of the token after each position of
    `decoder_ids` (B, T), which begins with the start token."""
    import jax.numpy as jnp

    eps, heads = config["layer_norm_epsilon"], config["num_heads"]
    dec = tree["decoder"]
    table = tree["shared_embedding"]["embedding"]
    t = decoder_ids.shape[1]
    bias = _bias(dec["rel_bias"]["embedding"], t, t, config,
                 bidirectional=False)
    causal = jnp.tril(jnp.ones((t, t), bool))[None]
    y = table[decoder_ids]
    for layer in dec["layers"]:
        h = _rms(layer["self_norm"], y, eps)
        y = y + _attend(layer["self_attention"], h, h, heads, bias, causal)
        h = _rms(layer["cross_norm"], y, eps)
        y = y + _attend(layer["cross_attention"], h, encoded, heads, None,
                        key_mask[:, None])
        y = y + _mlp(layer["mlp"], _rms(layer["mlp_norm"], y, eps))
    y = _rms(dec["final_norm"], y, eps)
    return (y / np.sqrt(config["d_model"])) @ table.T


def reference(params, config: dict, input_ids, lengths):
    """(encoder output (B, S, d_model), first-step logits (B, vocab))."""
    import jax
    import jax.numpy as jnp

    tree = _float32(params)
    with jax.default_matmul_precision("highest"):
        b, s = input_ids.shape
        key_mask = jnp.arange(s)[None, :] < lengths[:, None]
        encoded = _encode(tree, config, input_ids, key_mask)
        start = jnp.full((b, 1), config["decoder_start_token_id"], jnp.int32)
        return encoded, _decode(tree, config, encoded, key_mask, start)[:, 0]


def make_expected(params, config: dict, rng) -> dict:
    """A fixed sample of prompts and what the reference says of them
    (export child, on the CPU)."""
    import jax

    width = config["n_positions"]
    prompts = np.zeros((len(PROMPT_LENGTHS), width), np.int32)
    for row, n in enumerate(PROMPT_LENGTHS):
        n = min(n, width)
        prompts[row, :n] = rng.integers(2, config["vocab_size"], (n,))
    lengths = np.sum(prompts != config["pad_token_id"], -1).astype(np.int32)
    encoded, logits = jax.jit(
        lambda p, ids, lens: reference(p, config, ids, lens))(
            params, prompts, lengths)
    return {"prompts": prompts, "lengths": lengths,
            "encoded": np.asarray(encoded[:ENCODED]),
            "first_tokens": np.asarray(np.argmax(logits, -1), np.int32)}


def _run_session(ctx, sid: str, prompt, steps: int) -> list[int]:
    sid = np.asarray(sid.encode(), object)
    ctx.predict("decode_init", {"session_id": sid, "input_ids": prompt})
    tokens = [int(ctx.predict("decode_step", {"session_id": sid})["token"][0])
              for _ in range(steps)]
    ctx.predict("decode_close", {"session_id": sid})
    return tokens


def check(ctx) -> dict:
    """The served encoder against the reference; first tokens against the
    reference's; and, in a cell of sessions, the paged session streams
    against the same server's whole generation. Runs in the benchmark's
    parent (numpy only), outside the timed window; the same requests warm
    the programs they use (the 136-step session every table width). The
    whole generations and the session streams themselves are held to the
    reference in `verify`."""
    bar = ctx.config["correctness"]
    prompts, lengths = ctx.expected["prompts"], ctx.expected["lengths"]
    out: dict = {"ok": True, "seconds": {}}
    clock = time.monotonic()

    def lap(name):
        nonlocal clock
        out["seconds"][name] = time.monotonic() - clock
        clock = time.monotonic()

    got = ctx.predict("encode", {"input_ids": prompts})["encodings"]
    lap("encode")
    err = 0.0
    for row in range(len(ctx.expected["encoded"])):
        n = int(lengths[row])
        err = max(err, float(np.max(np.abs(
            got[row, :n] - ctx.expected["encoded"][row, :n]))))
    out["encoding_max_abs_diff"] = err
    out["ok"] &= bool(np.isfinite(got).all()) and err <= bar["encoding_atol"]

    whole = ctx.predict("serving_default",
                        {"input_ids": prompts})["output_ids"]
    first = whole[:, 0]
    # Held to the reference step by step in `verify`, after the window.
    ctx.deferred["output_ids"] = whole[:len(ctx.expected["encoded"])]
    lap("whole_generation")
    if ctx.traffic["kind"] == "sessions":
        # A few short sessions side by side (they share ticks and cross
        # two page boundaries), then one long session alone: a tick costs
        # what the pool costs, not what its riders cost, and riders that
        # alternate double the ticks, so the walk through every table
        # width is cheapest alone.
        limit = ctx.config["serve"]["signature_kwargs"]["max_decode_len"] - 1
        n = min(SHORT_SESSIONS, len(prompts) - 1)
        with cf.ThreadPoolExecutor(n) as pool:
            streams = [f.result() for f in [
                pool.submit(_run_session, ctx, f"check-{i}",
                            prompts[i:i + 1], min(SHORT_STEPS, limit))
                for i in range(n)]]
        streams.append(_run_session(ctx, f"check-{n}", prompts[n:n + 1],
                                    min(LONG_STEPS, limit)))
        # Two programs' greedy streams: one that parts from the whole
        # generation at a near-tie differs to its end and says nothing of
        # the steps after, so only whole streams are counted here; every
        # step of every stream is the reference's to judge (`verify`):
        # row i is prompt i's, -1 past its end.
        out["streams_identical"] = float(np.mean([
            all(a == int(b) for a, b in zip(stream, whole[i]))
            for i, stream in enumerate(streams)]))
        out["ok"] &= out["streams_identical"] >= bar["min_identical_streams"]
        held = np.full((len(streams), max(map(len, streams))), -1, np.int32)
        for i, stream in enumerate(streams):
            held[i, :len(stream)] = stream
        ctx.deferred["session_tokens"] = held
        lap("sessions")
    out["first_tokens_equal"] = float(np.mean(
        first == ctx.expected["first_tokens"]))
    out["ok"] &= out["first_tokens_equal"] >= bar["min_equal_first_tokens"]
    out["ok"] = bool(out["ok"])
    return out


def _steps_on_the_served_prefix(tree, config: dict, encoded, key_mask,
                                 served):
    """One served stream (T,) against the reference's decoder run once
    over it: for each step up to the first end-of-sequence token (after
    it the program pads), whether the reference's largest logit is the
    served token, and how far below the largest the served token's
    lies."""
    start = np.full((1, 1), config["decoder_start_token_id"], np.int32)
    given = np.concatenate([start, served[None, :-1]], axis=1)
    logits = np.asarray(_decode(tree, config, encoded, key_mask, given)[0])
    ended = np.flatnonzero(served == config["eos_token_id"])
    n = int(ended[0]) + 1 if ended.size else len(served)
    took = logits[np.arange(n), served[:n]]
    return (np.argmax(logits[:n], -1) == served[:n],
            np.max(logits[:n], -1) - took)


def verify(weights, config: dict, expected: dict, deferred: dict) -> dict:
    """Every step of what the served model generated against the
    reference (a CPU child, after the window): the whole generations
    (`output_ids`) and, from a cell of sessions, the tokens the decode
    sessions answered step by step (`session_tokens`, row i prompt i's,
    -1 past a stream's end). The decoder runs once over each served
    sequence, so step t sees the served tokens before t, and its largest
    logit should be the served token t: a greedy stream judged step by
    step, where one flipped near-tie costs one token and not the rest of
    the stream, and where a token that is not the reference's choice must
    at least be a near-tie in the reference's logits. The two surfaces
    are two programs, so each has its pair of numbers, under the same
    two limits. The encoder's output is the reference's own: kept at
    export for the whole generations' prompts, computed here for the
    sessions'."""
    import jax
    import jax.numpy as jnp

    bar = config["correctness"]
    tree = _float32({"decoder": weights("decoder"),
                     "shared_embedding": weights("shared_embedding")})
    lengths = np.asarray(expected["lengths"])
    found: dict = {"ok": True}

    def hold(surface: str, rows) -> None:
        """`rows`: (encoder output (1, S, d), key mask (1, S), served
        tokens (T,)) a stream."""
        equal, gaps = (np.concatenate(column) for column in zip(*(
            _steps_on_the_served_prefix(tree, config, *row)
            for row in rows)))
        share, gap = float(np.mean(equal)), float(np.max(gaps))
        found["ok"] &= bool(share >= bar["min_equal_generated_tokens"]
                            and gap <= bar["generated_logit_atol"])
        found.update({
            f"{surface}_tokens_equal": share,
            f"{surface}_tokens_compared": int(equal.size),
            # where the served token is not the reference's choice: how
            # far below the reference's largest logit its own lies (0 if
            # all agree). Rounding flips near-ties; a fault lands anywhere.
            f"{surface}_logit_gap_max": gap})

    with jax.default_matmul_precision("highest"):
        served = np.asarray(deferred["output_ids"], np.int32)
        encoded = jnp.asarray(expected["encoded"][:len(served)], jnp.float32)
        key_mask = jnp.arange(encoded.shape[1])[None, :] \
            < lengths[:len(served), None]
        hold("generated", [(encoded[row:row + 1], key_mask[row:row + 1],
                            served[row]) for row in range(len(served))])
        if "session_tokens" in deferred:
            streams = np.asarray(deferred["session_tokens"], np.int32)
            encoder = {"encoder": _float32(weights("encoder")),
                       "shared_embedding": tree["shared_embedding"]}
            ids = jnp.asarray(expected["prompts"][:len(streams)])
            key_mask = jnp.arange(ids.shape[1])[None, :] \
                < lengths[:len(streams), None]
            # One program for the five prompts: op by op, each prompt's
            # own length would compile every operation anew.
            encoded = jax.jit(lambda tree, ids, mask: _encode(
                tree, config, ids, mask))(encoder, ids, key_mask)
            hold("session", [(encoded[row:row + 1], key_mask[row:row + 1],
                              stream[stream >= 0])
                             for row, stream in enumerate(streams)])
    return found
