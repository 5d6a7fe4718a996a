"""The plain T5 reference against the program at a tiny size, on the
CPU: its encoder and first step, its decoder over a whole served
sequence (`verify`), whether a whole generation's or a decode session's,
and that `verify` tells a sound stream from a broken one."""

import copy
import json
import pathlib

import numpy as np
import pytest

from perfbench import children

ROOT = pathlib.Path(__file__).resolve().parents[2]
STEPS = 24


@pytest.fixture(scope="module")
def tiny():
    import jax

    from min_tfs_client_tpu.models import t5

    config = json.loads(
        (ROOT / "perfbench/configs/t5-large.json").read_text())
    config.update(d_model=64, d_kv=16, num_heads=4, d_ff=128, num_layers=2,
                  vocab_size=128, n_positions=32)
    config["serve"]["config_kwargs"]["num_decoder_layers"] = 2
    reference = children.load_reference(config)
    program_config = t5.T5Config(**children.program_config_kwargs(config))
    params = reference.adjust_params(
        t5.init_params(jax.random.PRNGKey(3), program_config), config)
    expected = reference.make_expected(params, config,
                                       np.random.default_rng(3))
    served, _ = jax.jit(lambda p, ids, lens: t5.greedy_decode(
        p, program_config, ids, lens, max_decode_len=STEPS))(
            params, expected["prompts"], expected["lengths"])
    return {"config": config, "reference": reference, "params": params,
            "expected": expected, "served": np.asarray(served),
            "program": (t5, program_config)}


def verify(tiny, served, config=None, sessions=None):
    rows = len(tiny["expected"]["encoded"])
    deferred = {"output_ids": served[:rows]}
    if sessions is not None:
        deferred["session_tokens"] = sessions
    return tiny["reference"].verify(
        lambda prefix: tiny["params"][prefix], config or tiny["config"],
        tiny["expected"], deferred)


SESSION_STEPS = (6, 6, 6, 6, 20)      # the check's shape: short ones, a long


def session_tokens(tiny):
    """What the check defers of its sessions: row i prompt i's stream,
    -1 past its end (here the program's own greedy tokens)."""
    held = np.full((len(SESSION_STEPS), max(SESSION_STEPS)), -1, np.int32)
    for row, steps in enumerate(SESSION_STEPS):
        held[row, :steps] = tiny["served"][row, :steps]
    return held


def test_the_encoder_agrees_with_the_programs(tiny):
    t5, program_config = tiny["program"]
    expected = tiny["expected"]
    got = np.asarray(t5.encode(tiny["params"], program_config,
                               expected["prompts"], expected["lengths"]),
                     np.float32)
    for row in range(len(expected["encoded"])):
        n = int(expected["lengths"][row])
        assert np.max(np.abs(got[row, :n] - expected["encoded"][row, :n])) \
            < 0.1


def test_first_tokens_agree_with_the_programs(tiny):
    assert np.mean(tiny["served"][:, 0]
                   == tiny["expected"]["first_tokens"]) >= 0.75


def test_every_step_of_a_served_generation_is_held_to_the_reference(tiny):
    found = verify(tiny, tiny["served"])
    assert found["ok"]
    assert found["generated_tokens_equal"] >= 0.9
    assert found["generated_tokens_compared"] == 2 * STEPS
    json.dumps(found)


def test_a_generation_of_other_tokens_is_refused(tiny):
    # At this size any repeated token is its own fixed point (the tied
    # embedding), so the broken stream is one that keeps changing.
    broken = np.random.default_rng(0).integers(
        2, 128, tiny["served"].shape).astype(np.int32)
    found = verify(tiny, broken)
    assert not found["ok"] and found["generated_tokens_equal"] < 0.25
    assert found["generated_logit_gap_max"] > 1.0


def test_one_wrong_token_fails_on_its_gap_not_on_the_share(tiny):
    one = tiny["served"].copy()
    one[0, 10] = 77 if one[0, 10] != 77 else 78
    found = verify(tiny, one)
    assert found["generated_tokens_equal"] >= 0.9
    assert found["generated_logit_gap_max"] > 1.0 and not found["ok"]
    lenient = copy.deepcopy(tiny["config"])
    lenient["correctness"]["generated_logit_atol"] = 100.0
    assert verify(tiny, one, lenient)["ok"]


def test_steps_after_the_end_of_sequence_are_not_counted(tiny):
    eos = tiny["config"]["eos_token_id"]
    ended = tiny["served"].copy()
    ended[0, 5] = eos
    ended[0, 6:] = tiny["config"]["pad_token_id"]
    assert verify(tiny, ended)["generated_tokens_compared"] == 6 + STEPS


def test_the_bar_comes_from_the_configurations_file(tiny):
    strict = copy.deepcopy(tiny["config"])
    strict["correctness"]["min_equal_generated_tokens"] = 1.01
    assert not verify(tiny, tiny["served"], strict)["ok"]


def test_a_step_sees_only_the_tokens_before_it(tiny):
    import jax.numpy as jnp

    reference, config = tiny["reference"], tiny["config"]
    expected = tiny["expected"]
    tree = reference._float32(tiny["params"])
    encoded = jnp.asarray(expected["encoded"][:1])
    mask = jnp.arange(encoded.shape[1])[None] < expected["lengths"][:1, None]
    a = np.array([[0, 5, 9, 11, 40, 41]], np.int32)
    b = a.copy()
    b[0, 4:] = (77, 78)
    la = np.asarray(reference._decode(tree, config, encoded, mask, a))
    lb = np.asarray(reference._decode(tree, config, encoded, mask, b))
    assert np.allclose(la[0, :4], lb[0, :4], atol=1e-5)
    assert not np.allclose(la[0, 4:], lb[0, 4:], atol=1e-5)


def test_session_streams_are_held_to_the_reference_step_by_step(tiny):
    found = verify(tiny, tiny["served"], sessions=session_tokens(tiny))
    assert found["ok"]
    assert found["session_tokens_compared"] == sum(SESSION_STEPS)
    assert found["session_tokens_equal"] >= 0.9
    assert found["session_logit_gap_max"] <= 1.0
    assert found["generated_tokens_compared"] == 2 * STEPS
    json.dumps(found)


@pytest.mark.parametrize("row, step", [(0, 0), (2, 5), (4, 0), (4, 19)])
def test_a_session_token_altered_where_it_is_produced_is_refused(
        tiny, row, step):
    broken = session_tokens(tiny)
    broken[row, step] = 77 if broken[row, step] != 77 else 78
    found = verify(tiny, tiny["served"], sessions=broken)
    assert not found["ok"] and found["session_logit_gap_max"] > 1.0
    # The whole generations were sound: the sessions' numbers alone say so.
    assert found["generated_logit_gap_max"] <= 1.0
    lenient = copy.deepcopy(tiny["config"])
    lenient["correctness"]["generated_logit_atol"] = 100.0
    assert verify(tiny, tiny["served"], lenient, sessions=broken)["ok"]


def test_a_session_that_parts_at_its_first_step_costs_one_token(tiny):
    """The 136-step stream of the cell's check parts from the whole
    generation at a tie between the reference's two largest logits and
    differs to its end. Judged on its OWN prefix it is sound: here the
    reference's runner-up at step 1, then the reference's greedy
    continuation of that."""
    import jax.numpy as jnp

    reference, config = tiny["reference"], tiny["config"]
    expected = tiny["expected"]
    row, steps = 4, SESSION_STEPS[4]
    n = int(expected["lengths"][row])
    tree = reference._float32(tiny["params"])
    seen = jnp.ones((1, n), bool)
    encoded = reference._encode(tree, config,
                                jnp.asarray(expected["prompts"][row:row + 1,
                                                                :n]), seen)
    given, margin = [config["decoder_start_token_id"]], None
    for step in range(steps):
        logits = np.asarray(reference._decode(
            tree, config, encoded, seen, np.asarray([given], np.int32)))[0, -1]
        order = np.argsort(logits)[::-1]
        if step == 0:
            margin = float(logits[order[0]] - logits[order[1]])
        given.append(int(order[1 if step == 0 else 0]))
    parted = session_tokens(tiny)
    parted[row, :steps] = given[1:]
    lenient = copy.deepcopy(tiny["config"])
    lenient["correctness"]["generated_logit_atol"] = margin + 1e-3
    found = verify(tiny, tiny["served"], lenient, sessions=parted)
    assert found["ok"]
    assert found["session_logit_gap_max"] == pytest.approx(margin, abs=1e-4)
    wrong = sum(SESSION_STEPS) * (1.0 - found["session_tokens_equal"])
    assert 1.0 - 1e-6 <= wrong <= 2.0   # the parted step (and at most a
    # rounding flip of the program's own elsewhere)


class FakeServer:
    """The check's view of a server: the tiny program's own answers; a
    session replays its prompt's greedy stream, or another where the
    test parts it."""

    def __init__(self, tiny, parted=()):
        t5, program_config = tiny["program"]
        expected = tiny["expected"]
        self.config = copy.deepcopy(tiny["config"])
        self.config["serve"]["signature_kwargs"]["max_decode_len"] = STEPS + 1
        self.traffic = {"kind": "sessions"}
        self.expected, self.deferred = expected, {}
        self.encodings = np.asarray(t5.encode(
            tiny["params"], program_config, expected["prompts"],
            expected["lengths"]), np.float32)
        self.whole = tiny["served"]
        self.parted, self.live = set(parted), {}

    def predict(self, signature, inputs):
        if signature == "encode":
            return {"encodings": self.encodings}
        if signature == "serving_default":
            return {"output_ids": self.whole}
        sid = inputs["session_id"].item().decode()
        if signature == "decode_init":
            row = int(sid.rsplit("-", 1)[1])
            assert (inputs["input_ids"]
                    == self.expected["prompts"][row:row + 1]).all()
            stream = self.whole[row] + (1 if row in self.parted else 0)
            self.live[sid] = iter(stream.tolist())
            return {}
        if signature == "decode_step":
            return {"token": np.asarray([next(self.live[sid])], np.int32)}
        assert signature == "decode_close"
        del self.live[sid]
        return {"closed": np.asarray(1)}


@pytest.mark.parametrize("parted, identical, ok", [
    ((), 1.0, True),
    ((4,), 0.8, True),        # one parted stream: the reference's to judge
    ((0, 1, 4), 0.4, False),  # most of them: a fault of paging, not a tie
])
def test_the_check_counts_whole_streams_and_defers_every_token(
        tiny, parted, identical, ok):
    ctx = FakeServer(tiny, parted)
    found = tiny["reference"].check(ctx)
    assert found["streams_identical"] == pytest.approx(identical)
    assert found["ok"] is ok and "tokens_equal" not in found
    assert not ctx.live                       # every session was closed
    held = ctx.deferred["session_tokens"]
    assert held.shape == (5, STEPS) and held.dtype == np.int32
    for row in range(5):
        assert (held[row] == tiny["served"][row]
                + (1 if row in parted else 0)).all()
    assert (ctx.deferred["output_ids"] == tiny["served"][:2]).all()


def test_what_the_check_defers_is_what_verify_holds(tiny):
    ctx = FakeServer(tiny, parted=(4,))
    assert tiny["reference"].check(ctx)["ok"]
    found = tiny["reference"].verify(
        lambda prefix: tiny["params"][prefix], tiny["config"],
        tiny["expected"], ctx.deferred)
    # A stream of every token moved by one is no near-tie: the check let
    # it through as one parted stream, the reference refuses it on its
    # first step (at this size the steps after it follow the prefix).
    assert not found["ok"] and found["session_logit_gap_max"] > 1.0
    assert found["generated_logit_gap_max"] <= 1.0
