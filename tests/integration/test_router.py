"""Routed fleet end-to-end: N real server subprocesses behind the
router process ("a server" -> "a service", docs/ROUTING.md).

The acceptance bar from the routing-tier issue, verified here:

 * Predict via the router is BIT-IDENTICAL to a direct connection (the
   data plane is a pure byte proxy; the unmodified client SDK talks to
   the router like it is one server);
 * decode sessions are sticky: every step lands on the process holding
   the session's state;
 * killing one backend loses no NEW requests once the client opts into
   the retry satellite, and the corpse is ejected within one poll
   interval of the first failed forward;
 * a SIGTERMed backend enters drain: NOT_SERVING on its health plane
   immediately, no new sessions, while its in-flight sessioned stream
   completes — then it exits cleanly.

Every test carries an explicit `proc_timeout` watchdog that SIGKILLs
all fleet subprocesses on expiry, so a hung wait fails fast with
connection errors instead of wedging the suite, and no orphaned
servers survive a failure (the CI satellite contract).
"""

import json
import pathlib
import threading
import time
import urllib.error
import urllib.request

import grpc
import numpy as np
import pytest

from min_tfs_client_tpu.client import TensorServingClient
from min_tfs_client_tpu.router.main import RouterOptions, RouterServer
from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray
from tests import fixtures

pytestmark = pytest.mark.integration

# Fleets register here so the per-test watchdog can hard-kill every
# subprocess on timeout — the no-orphans guarantee.
_ACTIVE_FLEETS: set = set()
_DEFAULT_TIMEOUT_S = 240


@pytest.fixture(autouse=True)
def _proc_watchdog(request):
    """Explicit per-test timeout for multi-process tests: on expiry,
    SIGKILL every registered fleet subprocess. Blocked gRPC/HTTP waits
    then fail immediately with UNAVAILABLE/connection-reset, turning a
    would-be hang into a loud failure with no leaked servers."""
    marker = request.node.get_closest_marker("proc_timeout")
    seconds = marker.args[0] if marker else _DEFAULT_TIMEOUT_S
    fired = threading.Event()

    def _fire():
        fired.set()
        for fleet in list(_ACTIVE_FLEETS):
            fleet.kill_all()

    timer = threading.Timer(seconds, _fire)
    timer.daemon = True
    timer.start()
    yield
    timer.cancel()
    assert not fired.is_set(), \
        f"proc_timeout watchdog fired after {seconds}s; fleet was killed"


def wait_until(predicate, timeout_s: float, message: str,
               interval_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval_s)
    raise AssertionError(f"timed out after {timeout_s}s: {message}")


# The subprocess boot/parse/teardown choreography is shared with bench's
# `routed` leg — one implementation in tests/fixtures.py.
ServerProc = fixtures.ModelServerProcess


class Fleet:
    """N server subprocesses + one in-process router, with guaranteed
    teardown (finalizer AND watchdog both funnel into kill_all)."""

    def __init__(self, tmp: pathlib.Path, n: int = 3,
                 drain_grace_s: float = 0.0,
                 poll_interval_s: float = 0.25,
                 data_plane: str = "aio"):
        self.poll_interval_s = poll_interval_s
        model_root = tmp / "model"
        fixtures.write_session_jax_servable(model_root)
        monitoring = tmp / "monitoring.config"
        monitoring.write_text("prometheus_config { enable: true }\n")
        self.servers = [ServerProc(model_root, monitoring,
                                   drain_grace_s=drain_grace_s)
                        for _ in range(n)]
        _ACTIVE_FLEETS.add(self)
        try:
            for server in self.servers:
                server.wait_ready()
            self.router = RouterServer(RouterOptions(
                grpc_port=0, rest_api_port=0,
                backends=",".join(s.backend_spec() for s in self.servers),
                health_poll_interval_s=poll_interval_s,
                probe_timeout_s=2.0,
                data_plane=data_plane,
            )).build_and_start()
        except BaseException:
            self.kill_all()
            raise
        self.by_pid = {s.pid: s for s in self.servers}
        self.by_backend_id = {f"127.0.0.1:{s.grpc_port}": s
                              for s in self.servers}

    # -- access --------------------------------------------------------------

    def client(self, **kw) -> TensorServingClient:
        return TensorServingClient("127.0.0.1", self.router.grpc_port,
                                   **kw)

    def direct_client(self, server: ServerProc) -> TensorServingClient:
        return TensorServingClient("127.0.0.1", server.grpc_port)

    def snapshot(self) -> dict:
        url = (f"http://127.0.0.1:{self.router.rest_port}"
               "/monitoring/router")
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    def states(self) -> dict[str, str]:
        return {bid: info["state"]
                for bid, info in self.snapshot()["backends"].items()}

    def wait_states(self, want, timeout_s: float = 30.0) -> None:
        """want: {backend_id_or_None: state}; None key = count of LIVE."""
        def check():
            states = self.states()
            return all(states.get(bid) == state
                       for bid, state in want.items())
        wait_until(check, timeout_s, f"states never reached {want}; "
                                     f"last: {self.states()}")

    def wait_live(self, n: int, timeout_s: float = 30.0) -> None:
        wait_until(
            lambda: sum(1 for s in self.states().values() if s == "LIVE")
            == n,
            timeout_s, f"never saw {n} LIVE backends: {self.states()}")

    # -- teardown ------------------------------------------------------------

    def kill_all(self) -> None:
        for server in self.servers:
            server.kill()

    def close(self) -> None:
        try:
            self.router.stop()
        except Exception:  # pragma: no cover - teardown best-effort
            pass
        self.kill_all()
        _ACTIVE_FLEETS.discard(self)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    f = Fleet(tmp_path_factory.mktemp("routed"), n=3)
    try:
        f.wait_live(3)
        yield f
    finally:
        f.close()


def _open_session(client, sid: bytes, base: int):
    resp = client.predict_request(
        "sess",
        {"session_id": np.asarray(sid, object),
         "base": np.asarray(base, np.int32)},
        signature_name="decode_init")
    return int(tensor_proto_to_ndarray(resp.outputs["pid"])[0])


def _step_session(client, sid: bytes):
    resp = client.predict_request(
        "sess", {"session_id": np.asarray(sid, object)},
        signature_name="decode_step")
    return (int(tensor_proto_to_ndarray(resp.outputs["token"])[0]),
            int(tensor_proto_to_ndarray(resp.outputs["pid"])[0]))


def _close_session(client, sid: bytes):
    client.predict_request(
        "sess", {"session_id": np.asarray(sid, object)},
        signature_name="decode_close")


@pytest.mark.proc_timeout(300)
class TestRoutedFleet:
    def test_fleet_ready_and_monitored(self, fleet):
        snap = fleet.snapshot()
        assert snap["ready"] is True
        assert len(snap["backends"]) == 3
        assert all(b["state"] == "LIVE" for b in snap["backends"].values())
        assert all("sess" in b["models"] for b in snap["backends"].values())
        occupancy = snap["ring"]["occupancy"]
        assert len(occupancy) == 3
        assert abs(sum(occupancy.values()) - 1.0) < 0.01

    def test_router_grpc_health(self, fleet):
        channel = grpc.insecure_channel(
            f"127.0.0.1:{fleet.router.grpc_port}")
        check = channel.unary_unary("/grpc.health.v1.Health/Check")
        assert check(b"", timeout=10) == bytes((0x08, 1))  # SERVING
        # per-model: "sess" is advertised by the polled readyz payloads
        request = bytes((0x0A, len(b"sess"))) + b"sess"
        assert check(request, timeout=10) == bytes((0x08, 1))
        with pytest.raises(grpc.RpcError) as err:
            check(bytes((0x0A, 5)) + b"ghost", timeout=10)
        assert err.value.code() == grpc.StatusCode.NOT_FOUND
        channel.close()

    def test_predict_bit_identical_vs_direct(self, fleet):
        """The proxy never re-serializes: the routed response must equal
        a direct connection's response byte for byte, on every backend
        (the fixture model is deterministic and identical fleet-wide)."""
        with fleet.client() as routed:
            for i in range(5):
                x = np.asarray([float(i), 2.5 * i, -i], np.float32)
                via_router = routed.predict_request("sess", {"x": x})
                np.testing.assert_allclose(
                    tensor_proto_to_ndarray(via_router.outputs["y"]),
                    x * 3.0 + 1.0)
                for server in fleet.servers:
                    with fleet.direct_client(server) as direct:
                        direct_resp = direct.predict_request(
                            "sess", {"x": x})
                    assert via_router.SerializeToString(
                        deterministic=True) == \
                        direct_resp.SerializeToString(deterministic=True)

    def test_rest_proxy_bit_identical(self, fleet):
        payload = json.dumps(
            {"instances": [{"x": 1.0}, {"x": 4.0}]}).encode()

        def post(port):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/sess:predict",
                data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.read()

        via_router = post(fleet.router.rest_port)
        assert json.loads(via_router)["predictions"] == [4.0, 13.0]
        for server in fleet.servers:
            assert via_router == post(server.rest_port)

    def test_sessions_sticky_and_spread(self, fleet):
        """Each session's every step lands on the process that served
        its init (token continuity proves the state never moved), and
        the fleet shares the session load."""
        with fleet.client() as client:
            owners = {}
            for i in range(12):
                sid = b"sticky-%d" % i
                owners[sid] = _open_session(client, sid, base=100 * i)
            for sid, owner_pid in owners.items():
                base = 100 * int(sid.split(b"-")[1])
                for step in range(1, 4):
                    token, pid = _step_session(client, sid)
                    assert pid == owner_pid, "session hopped backends"
                    assert token == base + step, \
                        "token stream broke: state was not continuous"
            assert len(set(owners.values())) >= 2, \
                "12 sessions all pinned to one backend"
            snap = fleet.snapshot()
            assert snap["sessions"]["total"] == 12
            for sid in owners:
                _close_session(client, sid)
            wait_until(lambda: fleet.snapshot()["sessions"]["total"] == 0,
                       10, "closes did not release session pins")

    def test_model_status_and_metadata_via_router(self, fleet):
        with fleet.client() as client:
            status = client.model_status_request("sess")
            assert status.model_version_status[0].state == 30  # AVAILABLE
            metadata = client.model_metadata_request("sess")
            assert metadata.model_spec.name == "sess"


@pytest.mark.proc_timeout(300)
class TestEjection:
    """Runs AFTER TestRoutedFleet (same module fleet): kills one backend
    for good."""

    def test_killed_backend_ejected_no_new_requests_lost(self, fleet):
        victim = fleet.servers[0]
        victim_id = f"127.0.0.1:{victim.grpc_port}"
        # a session pinned to the victim, to witness loss semantics
        with fleet.client() as plain:
            lost_sid = None
            for i in range(30):
                sid = b"doomed-%d" % i
                if _open_session(plain, sid, base=0) == victim.pid:
                    lost_sid = sid
                    break
            assert lost_sid is not None, \
                "30 sessions never landed on the victim backend"

        victim.kill()
        # New requests with the retry satellite: NONE may be lost, even
        # in the pre-eject window where the ring still names the corpse.
        with fleet.client(retry_unavailable=True, max_retries=5,
                          retry_backoff_s=0.1) as retrying:
            for i in range(30):
                x = np.asarray([float(i)], np.float32)
                resp = retrying.predict_request("sess", {"x": x})
                np.testing.assert_allclose(
                    tensor_proto_to_ndarray(resp.outputs["y"]),
                    x * 3.0 + 1.0)
            # eject: the first failed forward pulses the poll, so DEAD
            # within ~one poll interval (+ probe timeout slack)
            fleet.wait_states({victim_id: "DEAD"},
                              timeout_s=fleet.poll_interval_s * 2 + 5)
            # the pinned session died with its process: the pin is
            # dropped; its id now routes as a NEW session to a live
            # backend, which honestly reports the state is unknown
            with pytest.raises(grpc.RpcError) as err:
                _step_session(retrying, lost_sid)
            assert err.value.code() in (grpc.StatusCode.NOT_FOUND,
                                        grpc.StatusCode.UNAVAILABLE)
            # post-eject, plain clients (no retry) are clean too: the
            # ring no longer names the corpse
            for i in range(10):
                x = np.asarray([7.0 + i], np.float32)
                resp = retrying.predict_request("sess", {"x": x})
                np.testing.assert_allclose(
                    tensor_proto_to_ndarray(resp.outputs["y"]),
                    x * 3.0 + 1.0)
        snap = fleet.snapshot()
        assert snap["ready"] is True  # 2 of 3 still serving
        assert snap["ring"]["occupancy"].get(victim_id, 0.0) == 0.0


@pytest.mark.proc_timeout(300)
class TestThreadsPlaneEscapeHatch:
    def test_threads_plane_keeps_the_full_contract(self, tmp_path_factory):
        """--data_plane=threads (the pre-aio plane, kept one release;
        docs/MIGRATING.md): bit-identity, stickiness, and the monitoring
        surface all hold unchanged behind the flag."""
        f = Fleet(tmp_path_factory.mktemp("threads_plane"), n=2,
                  data_plane="threads")
        try:
            f.wait_live(2)
            assert f.snapshot()["data_plane"]["mode"] == "threads"
            with f.client() as client:
                x = np.asarray([1.0, -2.0, 0.5], np.float32)
                via_router = client.predict_request("sess", {"x": x})
                with f.direct_client(f.servers[0]) as direct:
                    direct_resp = direct.predict_request("sess", {"x": x})
                assert via_router.SerializeToString(deterministic=True) \
                    == direct_resp.SerializeToString(deterministic=True)
                owner = _open_session(client, b"th-0", base=5)
                for step in range(1, 4):
                    token, pid = _step_session(client, b"th-0")
                    assert (token, pid) == (5 + step, owner)
                _close_session(client, b"th-0")
        finally:
            f.close()


class TestRoutedAtMostOnce:
    def test_duplicate_resend_through_router_is_bit_identical(
            self, fleet):
        """The routed half of the at-most-once proof: a decode step
        carrying step_ordinal, re-sent THROUGH the router, returns the
        byte-identical PredictResponse and never advances the stream;
        an ordinal-less session on the same fleet behaves exactly as
        before (wire compat)."""
        with fleet.client() as client:
            sid = np.asarray(b"amo-routed", object)
            client.predict_request(
                "sess", {"session_id": sid,
                         "base": np.asarray(40, np.int32)},
                signature_name="decode_init")
            for step in range(1, 6):
                inputs = {"session_id": sid,
                          "step_ordinal": np.asarray(step, np.int64)}
                first = client.predict_request(
                    "sess", inputs, signature_name="decode_step")
                resend = client.predict_request(
                    "sess", inputs, signature_name="decode_step")
                assert first.SerializeToString(deterministic=True) == \
                    resend.SerializeToString(deterministic=True), \
                    "duplicate resend was not bit-identical"
                token = int(tensor_proto_to_ndarray(
                    first.outputs["token"])[0])
                assert token == 40 + step, \
                    "a duplicate resend advanced the stream"
            client.predict_request("sess", {"session_id": sid},
                                   signature_name="decode_close")
            # Ordinal-less behavior unchanged on the same surface.
            base = 70
            sid2 = np.asarray(b"amo-bare", object)
            client.predict_request(
                "sess", {"session_id": sid2,
                         "base": np.asarray(base, np.int32)},
                signature_name="decode_init")
            tokens = []
            for _ in range(3):
                resp = client.predict_request(
                    "sess", {"session_id": sid2},
                    signature_name="decode_step")
                tokens.append(int(tensor_proto_to_ndarray(
                    resp.outputs["token"])[0]))
            assert tokens == [base + 1, base + 2, base + 3]
            client.predict_request("sess", {"session_id": sid2},
                                   signature_name="decode_close")

    def test_out_of_order_ordinal_is_failed_precondition_on_wire(
            self, fleet):
        with fleet.client() as client:
            sid = np.asarray(b"amo-gap", object)
            client.predict_request(
                "sess", {"session_id": sid,
                         "base": np.asarray(0, np.int32)},
                signature_name="decode_init")
            client.predict_request(
                "sess", {"session_id": sid,
                         "step_ordinal": np.asarray(1, np.int64)},
                signature_name="decode_step")
            with pytest.raises(grpc.RpcError) as err:
                client.predict_request(
                    "sess", {"session_id": sid,
                             "step_ordinal": np.asarray(5, np.int64)},
                    signature_name="decode_step")
            assert err.value.code() == \
                grpc.StatusCode.FAILED_PRECONDITION
            client.predict_request("sess", {"session_id": sid},
                                   signature_name="decode_close")


class TestAioLoopShared:
    def test_second_aio_plane_in_one_process_shares_the_loop(
            self, fleet, tmp_path_factory):
        """ONE grpc.aio event loop per process (a second is a latent
        PollerCompletionQueue crash: BlockingIOError deep in cython,
        under load, long after boot). It used to be a typed refusal at
        start; now every plane runs on the process's one loop
        (utils/aio_loop.py), so a second router's aio plane starts
        beside the module fleet's live one, and both answer."""
        import threading

        from min_tfs_client_tpu.utils import aio_loop

        second = Fleet(tmp_path_factory.mktemp("second_aio"), n=1)
        try:
            second.wait_live(1)
            for f in (second, fleet, second):
                with f.client() as client:
                    sid = np.asarray(b"shared-loop", object)
                    client.predict_request(
                        "sess", {"session_id": sid,
                                 "base": np.asarray(7, np.int32)},
                        signature_name="decode_init")
                    client.predict_request("sess", {"session_id": sid},
                                           signature_name="decode_close")
            for f in (second, fleet):
                assert f.snapshot()["data_plane"]["mode"] == "aio"
            loops = [t.name for t in threading.enumerate()
                     if t.name == aio_loop.THREAD_NAME]
            assert loops == [aio_loop.THREAD_NAME]
        finally:
            second.close()

    def test_planes_start_and_stop_beside_each_other(self):
        """Stop/start cycles keep working on the shared loop, and a
        plane that stops takes nobody else's listener down."""
        from min_tfs_client_tpu.router.aio_proxy import AioDataPlane
        from min_tfs_client_tpu.router.core import RouterCore

        cores = [RouterCore([], poll_interval_s=3600.0) for _ in range(2)]
        first, follower = (AioDataPlane(core) for core in cores)
        port = first.start(0)
        assert port > 0
        other = follower.start(0)
        assert other not in (0, port)
        first.stop(grace=0.5)
        again = AioDataPlane(cores[0])
        assert again.start(0) > 0  # stopped: a new plane starts
        again.stop(grace=0.5)
        follower.stop(grace=0.5)
        follower.stop(grace=0.5)  # idempotent


@pytest.mark.proc_timeout(300)
class TestDrain:
    def test_sigterm_drains_sessions_then_exits(self, tmp_path_factory):
        """The full drain choreography on a fresh 2-backend fleet:
        SIGTERM -> NOT_SERVING immediately -> router stops sending new
        sessions -> the in-flight sessioned stream finishes against the
        draining process -> it exits cleanly once its sessions close."""
        # threads plane: the drain choreography under test is
        # plane-independent, and the threaded plane keeps a user here.
        f = Fleet(tmp_path_factory.mktemp("drain"), n=2,
                  drain_grace_s=30.0, data_plane="threads")
        try:
            f.wait_live(2)
            with f.client() as client:
                # pin one session on EACH backend so the drainer
                # provably holds in-flight state
                sessions_by_pid = {}
                for i in range(30):
                    sid = b"drain-%d" % i
                    pid = _open_session(client, sid, base=1000 * i)
                    sessions_by_pid.setdefault(pid, sid)
                    if len(sessions_by_pid) == 2:
                        break
                assert len(sessions_by_pid) == 2, \
                    "sessions never spread over both backends"
                victim = f.servers[0]
                survivor = f.servers[1]
                victim_sid = sessions_by_pid[victim.pid]
                victim_id = f"127.0.0.1:{victim.grpc_port}"

                victim.sigterm()
                # 1. the victim's own health plane flips NOT_SERVING
                #    while it still answers (that IS the flip-before-
                #    waiting contract)
                def victim_readyz():
                    url = (f"http://127.0.0.1:{victim.rest_port}"
                           "/monitoring/readyz")
                    try:
                        with urllib.request.urlopen(url, timeout=5):
                            return None
                    except urllib.error.HTTPError as err:
                        return json.loads(err.read())
                verdict = wait_until(victim_readyz, 15,
                                     "readyz never flipped during drain")
                assert verdict["draining"] is True
                assert any("draining" in r for r in verdict["reasons"])
                # 2. the router sees DRAINING (not DEAD: it still answers)
                f.wait_states({victim_id: "DRAINING"}, timeout_s=15)
                # 3. the in-flight sessioned stream still steps on the
                #    draining process
                base = 1000 * int(victim_sid.split(b"-")[1])
                for step in range(1, 6):
                    token, pid = _step_session(client, victim_sid)
                    assert pid == victim.pid
                    assert token == base + step
                # 4. NEW sessions never land on the drainer
                for i in range(10):
                    pid = _open_session(client, b"fresh-%d" % i, base=0)
                    assert pid == survivor.pid
                # 5. closing the drainer's last session lets it finish
                #    shutdown and exit cleanly
                _close_session(client, victim_sid)
                assert victim.proc.wait(timeout=60) == 0
                f.wait_states({victim_id: "DEAD"}, timeout_s=15)
                # the fleet keeps serving throughout
                x = np.asarray([3.0], np.float32)
                resp = client.predict_request("sess", {"x": x})
                np.testing.assert_allclose(
                    tensor_proto_to_ndarray(resp.outputs["y"]), [10.0])
        finally:
            f.close()
