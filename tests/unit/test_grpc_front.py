"""The gRPC front end's contract (server/server.py `_GrpcFront`,
server/grpc_services.py): a `grpc.aio` server on the process's ONE event
loop (utils/aio_loop.py). A request whose signature can await instead of
block (a decode_step of a pooled backend: its token is parked already,
or comes with the tick loop's round) is answered on the loop thread,
which goes on answering others while it awaits; every other request
runs on the worker pool. The model behind it is the toy decoder of
test_decode_signatures.py on the paged pool."""

from __future__ import annotations

import threading
import time

import grpc
import numpy as np
import pytest

from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.protos import grpc_service as gs
from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
from min_tfs_client_tpu.robustness import faults
from min_tfs_client_tpu.server.server import Server, ServerOptions
from min_tfs_client_tpu.tensor.codec import (
    ndarray_to_tensor_proto,
    tensor_proto_to_ndarray,
)
from min_tfs_client_tpu.utils import aio_loop
from min_tfs_client_tpu.utils.status import (
    Code,
    ServingError,
    to_grpc_code,
)
from tests.unit import test_decode_signatures as toy

SERVABLE_SRC = '''
"""The toy decoder's four session signatures on the paged pool, and a
host signature that sleeps."""
import time

import numpy as np

from min_tfs_client_tpu.servables.decode_sessions import Paging
from min_tfs_client_tpu.servables.decode_signatures import (
    build_session_signatures)
from min_tfs_client_tpu.servables.servable import Signature, TensorSpec
from tests.unit import test_decode_signatures as toy


def build(path):
    def slow_fn(inputs):
        time.sleep(float(np.asarray(inputs["seconds"]).reshape(-1)[0]))
        return {"slept": np.asarray(inputs["seconds"], np.float32)}

    signatures = build_session_signatures(
        toy._params(), toy.TOY, seq_len=toy.SEQ,
        max_decode_len=toy.MAXDEC, max_sessions=8,
        continuous_batching=True,
        paging=Paging(block_size=3, prefill_chunk=2))
    signatures["slow"] = Signature(
        fn=slow_fn, inputs={"seconds": TensorSpec(np.float32, ())},
        outputs={"slept": TensorSpec(np.float32, ())},
        on_host=True, batched=False)
    return signatures
'''


def _boot(tmp, name: str) -> Server:
    base = tmp / name
    (base / "1").mkdir(parents=True)
    (base / "1" / "servable.py").write_text(SERVABLE_SRC)
    monitoring = tmp / f"{name}.monitoring"
    monitoring.write_text("prometheus_config { enable: true }\n")
    return Server(ServerOptions(
        grpc_port=0, model_name="toy", model_base_path=str(base),
        model_platform="jax", monitoring_config_file=str(monitoring),
        rest_api_impl="python", grpc_max_threads=8,
        file_system_poll_wait_seconds=0, max_num_load_retries=0,
        watchdog=False, profile_sampler_hz=0.0,
        flush_filesystem_caches=False)).build_and_start()


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    srv = _boot(tmp_path_factory.mktemp("front"), "one")
    try:
        yield srv
    finally:
        srv.stop(grace=1.0)
        faults.disarm()


@pytest.fixture
def stub(server):
    with grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}") as channel:
        yield gs.PredictionServiceStub(channel)


def _request(signature: str, **inputs) -> apis.PredictRequest:
    request = apis.PredictRequest()
    request.model_spec.name = "toy"
    request.model_spec.signature_name = signature
    for alias, value in inputs.items():
        request.inputs[alias].CopyFrom(
            ndarray_to_tensor_proto(np.asarray(value)))
    return request


def _sid(name: str):
    return np.asarray(name.encode(), object)


def _open(stub, name: str, seed: int):
    ids = toy._prompt(seed)
    stub.Predict(_request("decode_init", session_id=_sid(name),
                          input_ids=ids), timeout=60)
    return ids


def _step(stub, name: str, **kw) -> int:
    extra = {k: kw.pop(k) for k in ("step_ordinal",) if k in kw}
    response = stub.Predict(
        _request("decode_step", session_id=_sid(name), **extra),
        timeout=60, **kw)
    return int(tensor_proto_to_ndarray(response.outputs["token"])[0])


def _close(stub, name: str) -> None:
    stub.Predict(_request("decode_close", session_id=_sid(name)),
                 timeout=60)


def _handlers(server):
    return server._grpc_front._handlers


def _await_parked(server, name: str, timeout_s: float = 60.0) -> None:
    """Until nothing is due in the tick loop and its thread has ended:
    every open session's next token is then parked."""
    from tests.fixtures import tick_loop_threads, until

    until(lambda: not tick_loop_threads(), timeout_s)


def _counts() -> tuple[int, int]:
    stats = aio_loop.stats()
    return stats["grpc_requests_inline"], stats["grpc_requests_pooled"]


def _step_traces(name: str) -> list:
    return [t for t in tracing.ring_snapshot()
            if t.signature == "decode_step"
            and t.meta.get("session_id") == name]


def _wait_span(trace) -> dict:
    (args,) = [a for n, _, _, a in trace.spans if n == "decode/wait"]
    return args


def _hold_next_round(seconds: float) -> None:
    faults.arm({"rules": [{"point": "backend.tick.pre", "action": "delay",
                           "delay_ms": seconds * 1e3, "max_fires": 1}]})


# -- one loop a process ---------------------------------------------------------


def test_two_servers_and_two_router_planes_share_one_loop(
        server, tmp_path_factory):
    """gRPC's completion queue takes ONE asyncio loop a process. Two
    ModelServers and two routers' aio planes in this process all answer,
    from one loop thread."""
    from min_tfs_client_tpu.router.main import RouterOptions, RouterServer

    other = _boot(tmp_path_factory.mktemp("front2"), "two")
    routers = []
    try:
        for srv in (server, other):
            routers.append(RouterServer(RouterOptions(
                grpc_port=0, rest_api_port=0, data_plane="aio",
                backends=f"127.0.0.1:{srv.grpc_port}:{srv.rest_port}",
                health_poll_interval_s=0.1, probe_timeout_s=2.0,
                profile_sampler_hz=0.0)).build_and_start())
        ports = [server.grpc_port, other.grpc_port,
                 *(r.grpc_port for r in routers)]
        assert len(set(ports)) == 4
        for i, port in enumerate(ports):
            with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
                front = gs.PredictionServiceStub(channel)
                deadline = time.monotonic() + 30.0
                while True:  # a router first has to see its backend live
                    try:
                        ids = _open(front, f"shared-{i}", seed=i + 1)
                        break
                    except grpc.RpcError as err:
                        assert err.code() == grpc.StatusCode.UNAVAILABLE
                        assert time.monotonic() < deadline
                        time.sleep(0.1)
                stream = [_step(front, f"shared-{i}") for _ in range(3)]
                _close(front, f"shared-{i}")
                assert stream == toy._reference(toy._params(), ids, 3)
        loops = [t for t in threading.enumerate()
                 if t.name == aio_loop.THREAD_NAME]
        assert len(loops) == 1
        assert not [t for t in threading.enumerate()
                    if t.name == "router-aio-data-plane"]
    finally:
        for router in routers:
            router.stop()
        other.stop(grace=1.0)


# -- the choice between loop and pool ---------------------------------------------


def test_a_parked_step_is_answered_on_the_loop_thread(server, stub):
    ids = _open(stub, "parked", seed=3)
    stream = []
    for _ in range(3):
        _await_parked(server, "parked")
        inline, pooled = _counts()
        stream.append(_step(stub, "parked"))
        assert _counts() == (inline + 1, pooled)
    assert stream == toy._reference(toy._params(), ids, 3)
    traces = _step_traces("parked")
    assert len(traces) == 3
    for trace in traces:
        assert trace.transport == "grpc" and trace.status == "0"
        assert _wait_span(trace)["inline"] == 1
        assert _wait_span(trace)["ahead"] == 1
    inline, pooled = _counts()
    _close(stub, "parked")      # its signature has no form that awaits
    assert _counts() == (inline, pooled + 1)


def test_the_runtime_endpoint_shows_the_counts_and_the_lag(server, stub):
    from min_tfs_client_tpu.observability import runtime

    _open(stub, "shown", seed=4)
    _step(stub, "shown")
    _close(stub, "shown")
    deadline = time.monotonic() + 10.0
    while "event_loop_lag_p99_ms" not in runtime.snapshot()["grpc"]:
        assert time.monotonic() < deadline, "the lag ticker never ticked"
        time.sleep(0.05)
    block = runtime.snapshot()["grpc"]
    assert block["grpc_requests_inline"] >= 1
    assert block["grpc_requests_pooled"] >= 2
    assert 0.0 <= block["event_loop_lag_p50_ms"] \
        <= block["event_loop_lag_p99_ms"] <= block["event_loop_lag_max_ms"]
    assert block["lag_samples"] >= 1


def test_a_step_whose_round_is_held_awaits_on_the_loop_which_answers_on(
        server, stub):
    """A round held at the `backend.tick.pre` faultpoint: the step that
    needs its token awaits it ON the loop thread, no pool thread is
    taken, and the loop goes on answering other sessions meanwhile."""
    ids = _open(stub, "held", seed=5)
    _open(stub, "bystander", seed=6)
    _await_parked(server, "held")
    _hold_next_round(1.5)
    try:
        first = _step(stub, "held")     # parked; its next round is now
        out = {}                        # held at the faultpoint
        waiter = threading.Thread(
            target=lambda: out.update(token=_step(stub, "held")),
            name="held-step")
        inline, pooled = _counts()
        t0 = time.monotonic()
        waiter.start()
        time.sleep(0.1)
        _step(stub, "bystander")        # parked: answered meanwhile
        assert waiter.is_alive() and time.monotonic() - t0 < 1.0
        assert _counts() == (inline + 2, pooled)
        waiter.join(timeout=30)
        assert not waiter.is_alive() and time.monotonic() - t0 >= 1.0
    finally:
        faults.disarm()
    assert [first, out["token"]] == toy._reference(toy._params(), ids, 2)
    held = _step_traces("held")
    assert [_wait_span(t)["inline"] for t in held] == [1, 1]
    assert [t.status for t in held] == ["0", "0"]
    _close(stub, "held")
    _close(stub, "bystander")


def test_a_resend_that_races_an_awaiting_step_does_not_block_the_loop(
        server, stub):
    """While a guarded step awaits its round on the loop, a resend of
    the same ordinal is answered at once, typed retryable, as it is
    beside a step that waits on a pool thread; the original then ends
    with its token, and a resend after it replays that token."""
    ids = _open(stub, "raced", seed=7)
    _await_parked(server, "raced")
    _hold_next_round(1.0)
    try:
        first = _step(stub, "raced", step_ordinal=np.asarray(1, np.int64))
        out = {}
        waiter = threading.Thread(
            target=lambda: out.update(token=_step(
                stub, "raced", step_ordinal=np.asarray(2, np.int64))),
            name="raced-step")
        t0 = time.monotonic()
        waiter.start()
        time.sleep(0.1)
        with pytest.raises(grpc.RpcError) as err:
            _step(stub, "raced", step_ordinal=np.asarray(2, np.int64))
        assert err.value.code() == grpc.StatusCode.UNAVAILABLE
        assert "already executing" in err.value.details()
        assert waiter.is_alive() and time.monotonic() - t0 < 0.8
        waiter.join(timeout=30)
        assert not waiter.is_alive()
    finally:
        faults.disarm()
    again = _step(stub, "raced", step_ordinal=np.asarray(2, np.int64))
    assert [first, out["token"]] == toy._reference(toy._params(), ids, 2)
    assert again == out["token"]
    _close(stub, "raced")


def test_a_client_that_gives_up_cancels_its_rpc_and_not_its_step(
        server, stub):
    """The deadline of a step that awaits its round passes: the client
    gets DEADLINE_EXCEEDED, the step ends on the server as it would on
    a pool thread, and the resend of its ordinal replays its token."""
    ids = _open(stub, "gone", seed=12)
    _await_parked(server, "gone")
    _hold_next_round(1.0)
    try:
        first = _step(stub, "gone", step_ordinal=np.asarray(1, np.int64))
        with pytest.raises(grpc.RpcError) as err:
            stub.Predict(
                _request("decode_step", session_id=_sid("gone"),
                         step_ordinal=np.asarray(2, np.int64)), timeout=0.2)
        assert err.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    finally:
        faults.disarm()
    deadline = time.monotonic() + 30.0
    while True:     # until the step has ended: then its answer replays
        try:
            second = _step(stub, "gone",
                           step_ordinal=np.asarray(2, np.int64))
            break
        except grpc.RpcError as err:
            assert err.code() == grpc.StatusCode.UNAVAILABLE
            assert time.monotonic() < deadline
            time.sleep(0.05)
    third = _step(stub, "gone", step_ordinal=np.asarray(3, np.int64))
    assert [first, second, third] == toy._reference(toy._params(), ids, 3)
    _close(stub, "gone")


def test_a_predict_that_blocks_on_the_pool_does_not_delay_a_step_on_the_loop(
        server, stub):
    _open(stub, "quick", seed=9)
    _await_parked(server, "quick")
    sleeper = threading.Thread(
        target=lambda: stub.Predict(
            _request("slow", seconds=np.float32(2.0)), timeout=60),
        name="slow-predict")
    t0 = time.monotonic()
    sleeper.start()
    time.sleep(0.2)
    _step(stub, "quick")
    assert sleeper.is_alive() and time.monotonic() - t0 < 1.0
    sleeper.join(timeout=30)
    assert not sleeper.is_alive() and time.monotonic() - t0 >= 2.0
    _close(stub, "quick")


# -- what rides along ---------------------------------------------------------------


@pytest.mark.parametrize("on_loop", [True, False],
                         ids=["on_the_loop", "on_the_pool"])
def test_the_propagated_trace_id_is_adopted(server, stub, on_loop):
    name = f"traced-{int(on_loop)}"
    trace_id = f"fleet-{int(on_loop)}-0123456789"
    metadata = ((tracing.TRACE_HEADER, trace_id),)
    if on_loop:
        _open(stub, name, seed=10)
        _step(stub, name, metadata=metadata)
        (trace,) = tracing.find_traces(trace_id)
        assert trace.signature == "decode_step"
        assert _wait_span(trace)["inline"] == 1
    else:
        stub.Predict(_request("decode_init", session_id=_sid(name),
                              input_ids=toy._prompt(10)),
                     timeout=60, metadata=metadata)
        (trace,) = tracing.find_traces(trace_id)
        assert trace.signature == "decode_init"
    assert trace.transport == "grpc" and trace.status == "0"
    _close(stub, name)


ERRORS = [ServingError(code, f"refused with {name}")
          for name, code in Code.items() if code != Code.OK]
ERRORS += [ValueError("a bad value"), RuntimeError("a surprise"),
           NotImplementedError("not here"), TimeoutError("too late")]


@pytest.mark.parametrize(
    "error", ERRORS,
    ids=[Code.Name(e.code) if isinstance(e, ServingError)
         else type(e).__name__ for e in ERRORS])
def test_a_handler_error_reaches_the_client_as_guard_mapped_it(
        server, stub, monkeypatch, error):
    """Code and message of the synchronous `_guard`, on both paths."""
    from min_tfs_client_tpu.utils.status import error_from_exception

    handlers = _handlers(server)

    def refuse(request):
        raise error

    async def arefuse(request):
        raise error

    monkeypatch.setattr(handlers, "predict", refuse)
    monkeypatch.setattr(handlers, "apredict", arefuse)
    want = error_from_exception(error)
    for on_loop in (True, False):
        monkeypatch.setattr(handlers, "can_await",
                            lambda request, _v=on_loop: _v)
        with pytest.raises(grpc.RpcError) as err:
            stub.Predict(_request("decode_step", session_id=_sid("none")),
                         timeout=30)
        assert err.value.code() == to_grpc_code(want.code)
        assert err.value.details() == want.message


def test_an_unknown_session_is_not_found_from_the_loop(server, stub):
    """A step's own typed error, through the awaiting form: the code and
    text of the blocking form, and an error trace like any other."""
    inline, pooled = _counts()
    with pytest.raises(grpc.RpcError) as err:
        _step(stub, "never-opened")
    assert err.value.code() == grpc.StatusCode.NOT_FOUND
    assert "never-opened" in err.value.details()
    assert _counts() == (inline + 1, pooled)
    (trace,) = _step_traces("never-opened")
    assert trace.status == str(Code.NOT_FOUND)


def test_an_armed_fault_on_the_handlers_keeps_a_step_off_the_loop(
        server, stub):
    """A fault rule that names `backend.handle.pre` may sleep there: the
    request runs on the pool, where it may."""
    _open(stub, "faulted", seed=11)
    faults.arm({"rules": [{"point": "backend.handle.pre",
                           "action": "delay", "delay_ms": 1.0}]})
    try:
        inline, pooled = _counts()
        _step(stub, "faulted")
        assert _counts() == (inline, pooled + 1)
    finally:
        faults.disarm()
    (trace,) = _step_traces("faulted")
    assert _wait_span(trace)["inline"] == 0
    _close(stub, "faulted")


def test_the_other_services_answer_from_the_pool(server):
    """Synchronous servicers, unchanged, on the aio server's pool."""
    with grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}") as channel:
        request = apis.GetModelStatusRequest()
        request.model_spec.name = "toy"
        status = gs.ModelServiceStub(channel).GetModelStatus(
            request, timeout=30)
        assert [v.version for v in status.model_version_status] == [1]
        request.model_spec.name = "nobody"
        with pytest.raises(grpc.RpcError) as err:
            gs.ModelServiceStub(channel).GetModelStatus(request, timeout=30)
        assert err.value.code() == grpc.StatusCode.NOT_FOUND
        meta = apis.GetModelMetadataRequest()
        meta.model_spec.name = "toy"
        meta.metadata_field.append("signature_def")
        got = gs.PredictionServiceStub(channel).GetModelMetadata(
            meta, timeout=30)
        assert got.model_spec.version.value == 1
        check = channel.unary_unary("/grpc.health.v1.Health/Check")
        # SERVING or NOT_SERVING: the verdict is the process's, and other
        # servers of this module have stopped in it.
        assert check(b"", timeout=30) in (b"\x08\x01", b"\x08\x02")


# -- lifecycle ----------------------------------------------------------------------


def test_stop_with_grace_drains(tmp_path_factory):
    """An RPC in flight at stop() finishes inside the grace; a new one
    is refused; stop() returns once the one in flight has."""
    srv = _boot(tmp_path_factory.mktemp("front3"), "three")
    channel = grpc.insecure_channel(f"127.0.0.1:{srv.grpc_port}")
    try:
        front = gs.PredictionServiceStub(channel)
        front.Predict(_request("slow", seconds=np.float32(0.0)), timeout=30)
        out = {}
        inflight = threading.Thread(
            target=lambda: out.update(response=front.Predict(
                _request("slow", seconds=np.float32(1.0)), timeout=30)),
            name="inflight-predict")
        inflight.start()
        time.sleep(0.2)
        t0 = time.monotonic()
        srv.stop(grace=10.0)
        assert 0.5 <= time.monotonic() - t0 < 8.0
        inflight.join(timeout=30)
        assert float(tensor_proto_to_ndarray(
            out["response"].outputs["slept"])) == 1.0
        with pytest.raises(grpc.RpcError) as err:
            front.Predict(_request("slow", seconds=np.float32(0.0)),
                          timeout=5)
        assert err.value.code() == grpc.StatusCode.UNAVAILABLE
        srv.stop(grace=1.0)  # idempotent
    finally:
        channel.close()
