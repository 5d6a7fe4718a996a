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


class FakeChild:
    def __init__(self, found, says):
        self.found, self.says, self.returncode = found, says, 0

    def wait(self):
        self.found.write_text(json.dumps(self.says))
        return self.returncode


@pytest.mark.parametrize("check_ok, child_ok", [
    (True, True), (True, False), (False, True), (False, False)])
def test_a_check_that_failed_stays_failed_whatever_the_child_finds(
        tmp_path, monkeypatch, check_ok, child_ok):
    """Until PR 45 `and` cut short before the child's `ok` was popped,
    and the update wrote the child's true over the check's false."""
    np.savez(tmp_path / "deferred.npz", output_ids=np.zeros((1, 4), np.int32))
    monkeypatch.setattr(run.srv, "spawn", lambda command, env: FakeChild(
        pathlib.Path(command[-1]), {"ok": child_ok, "gap": 0.5}))
    verdict = {"ok": check_ok, "seconds": {}}
    run.verify_deferred({"config_file": tmp_path / "none.json"},
                        tmp_path, tmp_path, verdict)
    assert verdict["ok"] is (check_ok and child_ok)
    assert verdict["gap"] == 0.5 and verdict["seconds"]["verify"] >= 0.0


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

    sessions = np.full((5, 8), -1, np.int32)   # the sessions cell's extra
    sessions[:4, :3], sessions[4] = 5, 5
    np.savez(tmp_path / "deferred.npz",
             output_ids=np.full((2, 8), 5, np.int32),
             session_tokens=sessions)
    found = tmp_path / "verified.json"
    subprocess.run([sys.executable, script, "verify", str(config_file),
                    str(out), str(tmp_path / "deferred.npz"), str(found)],
                   env=env, check=True, timeout=600)
    got = json.loads(found.read_text())
    assert sorted(got) == ["generated_logit_gap_max",
                           "generated_tokens_compared",
                           "generated_tokens_equal", "ok",
                           "session_logit_gap_max",
                           "session_tokens_compared",
                           "session_tokens_equal"]
    assert got["generated_tokens_compared"] == 16
    assert got["session_tokens_compared"] == 4 * 3 + 8


def snapshot(at, gc, over, server_cpu):
    return {"at": at, "server_cpu_s": server_cpu, "loadavg": [1.0, 1.0, 1.0],
            "runtime": {"gc_pause_seconds": gc, "grpc": {
                "lag_over_threshold": over, "event_loop_cpu_share": 0.85}}}


def test_the_whole_window_s_account_of_the_host():
    records = {"requests": [], "generator": {"cpu_cores": 0.9},
               "sessions": [
                   {"due": -3.0, "length": 100,
                    "steps": [0.01 * i for i in range(4000)]},
                   {"due": 2.0, "length": 300, "steps": [39.0, 41.0]}]}
    found = run.whole_window(
        snapshot(100.0, {"0": 1.0, "2": 2.0}, 1, 10.0),
        snapshot(140.0, {"0": 1.5, "2": 3.0}, 7, 90.0),
        records, 40.0)
    assert found["gc_pause_s"] == {"0": 0.5, "2": 1.0}
    assert found["event_loop_stalls"] == 6
    assert found["server_cpu_cores"] == pytest.approx(2.0)
    assert found["generator"] == {"cpu_cores": 0.9}
    assert found["outputs_per_s_by_eighth"] == pytest.approx(
        [100.0] * 7 + [100.2])


def test_a_process_s_cpu_seconds_or_nothing():
    assert run.process_cpu_s(os.getpid()) >= 0.0
    assert run.process_cpu_s(2**22 + 12345) is None


@pytest.mark.parametrize("cache_after_each_check, boots", [
    ([5], 1),        # every program was in the cache: the first server stays
    ([9, 9], 2),     # set-up compiled four: a second server loads them all
    ([9, 10], 2),    # the second compiled one more: it stays all the same
    ([-4], 2),       # a cache held to its size: four new for four old
])
def test_the_server_that_meets_the_window_compiled_nothing_if_one_more_boot_can_help(
        tmp_path, monkeypatch, cache_after_each_check, boots):
    def names(n):   # n programs; a negative n: as many of them NEW ones
        return {f"p{i}-cache" for i in range(*((5, 5 - n) if n < 0 else (n,)))}

    if cache_after_each_check == [-4]:
        cache_after_each_check = [-4, -4]
        want = [4, 0]
    else:
        want = [b - a for a, b in zip([5] + cache_after_each_check,
                                      cache_after_each_check)]
    cache = iter([names(5)] + [names(n) for n in cache_after_each_check
                               for _ in (0, 1)])
    servers = []

    def start(spec, export_dir, trace_ring):
        servers.append(types.SimpleNamespace(
            stopped=False, terminate=lambda n=len(servers): setattr(
                servers[n], "stopped", True)))
        return servers[-1], {"platform": "tpu", "count": 1}

    monkeypatch.setattr(run, "WORK", tmp_path)
    monkeypatch.setattr(run, "ensure_export", lambda *a: tmp_path / "export")
    monkeypatch.setattr(run, "start_server", start)
    monkeypatch.setattr(run, "check_and_warm",
                        lambda *a: {"ok": True, "by": len(servers)})
    monkeypatch.setattr(run, "cached_programs", lambda: next(cache))
    server, export_dir, device, verdict, spent = run.boot_check_and_warm(
        {"config": {}, "config_file": tmp_path / "c.json"}, 0)
    assert len(servers) == boots and server is servers[-1]
    assert [s.stopped for s in servers] == [True] * (boots - 1) + [False]
    assert verdict == {"ok": True, "by": boots}     # the one that stays
    assert spent["programs_compiled"] == want
    assert (tmp_path / "run" / "profile").is_dir()


def test_cached_programs_counts_the_cache_s_entries(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for name in ("jit_a-1f-cache", "jit_b-2e-cache", "jit_b-2e-atime"):
        (tmp_path / name).write_text("")
    assert run.cache_dir() == tmp_path
    assert run.cached_programs() == {"jit_a-1f-cache", "jit_b-2e-cache"}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert run.cache_dir() == run.WORK / "jax_cache"
