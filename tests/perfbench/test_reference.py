"""The plain T5 reference against the program at a tiny size, on the
CPU: its encoder and first step, its decoder over a whole served
sequence (`verify`), and that `verify` tells a sound generation from a
broken one."""

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


def verify(tiny, served, config=None):
    rows = len(tiny["expected"]["encoded"])
    return tiny["reference"].verify(
        lambda prefix: tiny["params"][prefix], config or tiny["config"],
        tiny["expected"], {"output_ids": served[:rows]})


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
