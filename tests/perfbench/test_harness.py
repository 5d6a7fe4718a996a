"""The harness around a configuration's reference: the export's loader
stub and the notice once it drops no name, what a check defers to the
CPU child after the window, and the export and verify children at a tiny
size on the CPU."""

import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import children, run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def server_with_log(tmp_path, text):
    log = tmp_path / "server.log"
    log.write_text(text)
    return types.SimpleNamespace(log=log)


@pytest.mark.parametrize("text, want, notice", [
    ("boot\nperfbench-stub: kept 7 of 8 signature names\nserving\n",
     {"names_kept": 7, "names_given": 8}, False),
    ("perfbench-stub: kept 8 of 8 signature names\n",
     {"names_kept": 8, "names_given": 8}, True),
    ("a server that loaded through another stub\n", None, False),
])
def test_the_alias_workaround_gives_notice_once_it_drops_nothing(
        tmp_path, capsys, text, want, notice):
    assert run.alias_workaround(server_with_log(tmp_path, text)) == want
    assert ("should remove _SERVABLE_STUB" in capsys.readouterr().err) \
        == notice


def test_the_loader_stub_keeps_one_name_for_each_signature(monkeypatch,
                                                          capsys):
    from min_tfs_client_tpu.models import export

    one, two = object(), object()
    monkeypatch.setattr(export, "load_signatures", lambda path: {
        "serving_default": one, "decode": one, "encode": two})
    scope: dict = {}
    exec(children._SERVABLE_STUB, scope)
    assert scope["build"]("anywhere") == {"serving_default": one,
                                          "encode": two}
    assert "perfbench-stub: kept 2 of 3 signature names" \
        in capsys.readouterr().out


def test_a_check_that_deferred_nothing_costs_no_child(tmp_path):
    verdict = {"ok": True, "seconds": {}}
    run.verify_deferred({"config_file": tmp_path / "none.json"},
                        tmp_path, tmp_path, verdict)
    assert verdict == {"ok": True, "seconds": {}}


def test_a_verify_child_that_fails_makes_the_run_incorrect(tmp_path):
    np.savez(tmp_path / "deferred.npz", output_ids=np.zeros((1, 4), np.int32))
    verdict = {"ok": True, "seconds": {}}
    run.verify_deferred({"config_file": tmp_path / "none.json"},
                        tmp_path, tmp_path, verdict)
    assert verdict["ok"] is False and verdict["verify_child_rc"] != 0
    assert verdict["seconds"]["verify"] > 0.0


def test_export_and_verify_children_at_a_tiny_size(tmp_path):
    config = json.loads(
        (ROOT / "perfbench/configs/t5-large.json").read_text())
    config.update(d_model=32, d_kv=8, num_heads=4, d_ff=64, num_layers=1,
                  vocab_size=64, n_positions=32)
    config["serve"]["config_kwargs"]["num_decoder_layers"] = 1
    config["serve"]["signature_kwargs"].update(seq_len=32, max_decode_len=8,
                                               max_sessions=2)
    config_file = tmp_path / "t5-tiny.json"
    config_file.write_text(json.dumps(config))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = str(ROOT / "perfbench" / "children.py")
    out = tmp_path / "export"
    out.mkdir()
    subprocess.run([sys.executable, script, "export", str(config_file),
                    str(out)], env=env, check=True, timeout=600)
    assert (out / "DONE").exists()
    version = out / config["serve"]["model_name"] / "1"
    assert "perfbench-stub" in (version / "servable.py").read_text()
    expected = np.load(out / "expected.npz")
    assert expected["encoded"].shape == (2, 32, 32)
    assert expected["first_tokens"].shape == (8,)

    np.savez(tmp_path / "deferred.npz",
             output_ids=np.full((2, 8), 5, np.int32))
    found = tmp_path / "verified.json"
    subprocess.run([sys.executable, script, "verify", str(config_file),
                    str(out), str(tmp_path / "deferred.npz"), str(found)],
                   env=env, check=True, timeout=600)
    got = json.loads(found.read_text())
    assert sorted(got) == ["generated_logit_gap_max",
                           "generated_tokens_compared",
                           "generated_tokens_equal", "ok"]
    assert got["generated_tokens_compared"] == 16
