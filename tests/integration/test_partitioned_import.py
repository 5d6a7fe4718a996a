"""Host/device partitioning of imported graphs (VERDICT round-5 #1).

A transformer-style classify export — ParseExample -> embedding ->
self-attention block -> pooled logits -> softmax -> string-label hash
table — previously served 100% on numpy because ONE string op anywhere
put the whole signature on host. The partition must place the dense
interior in a jitted device function (asserted via the interior jaxpr:
dot_general present) while the label lookup stays host, with numerics
cross-validated against TF's own Session. Reference parity:
common_runtime/placer.h:55 (string kernels on CPU, dense on device
within one graph), servables/tensorflow/classifier.h:16-90.
"""

from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

from min_tfs_client_tpu.client import TensorServingClient
from min_tfs_client_tpu.server.server import Server, ServerOptions
from min_tfs_client_tpu.servables.graphdef_import import load_saved_model
from min_tfs_client_tpu.tensor.example_codec import example_from_dict

EXPORT_SCRIPT = """
import sys
import numpy as np
import tensorflow as tf

tf1 = tf.compat.v1
tf1.disable_eager_execution()

export_dir, examples_path, out_path = sys.argv[1:4]
payloads = np.load(examples_path, allow_pickle=True)

SEQ, VOCAB, D, CLASSES = 6, 32, 16, 4

g = tf1.Graph()
with g.as_default():
    serialized = tf1.placeholder(tf.string, [None],
                                 name="input_example_tensor")
    features = tf1.io.parse_example(serialized, {
        "ids": tf1.io.FixedLenFeature([SEQ], tf.int64)})
    rng = np.random.default_rng(41)

    def var(name, shape):
        return tf1.get_variable(
            name, initializer=(rng.standard_normal(shape) * 0.3
                               ).astype(np.float32))

    emb = var("emb", (VOCAB, D))
    x = tf.gather(emb, features["ids"])          # [B, S, D]
    # One self-attention block (the BERT shape, tiny dims).
    q = tf.einsum("bsd,de->bse", x, var("wq", (D, D)))
    k = tf.einsum("bsd,de->bse", x, var("wk", (D, D)))
    v = tf.einsum("bsd,de->bse", x, var("wv", (D, D)))
    att = tf.nn.softmax(
        tf.matmul(q, k, transpose_b=True) / np.float32(np.sqrt(D)))
    ctx = tf.matmul(att, v) + x                  # residual
    h = tf.nn.relu(tf.einsum("bsd,de->bse", ctx, var("wf", (D, D))))
    pooled = tf.reduce_mean(h, axis=1)           # [B, D]
    logits = tf.matmul(pooled, var("wo", (D, CLASSES)))
    scores = tf.nn.softmax(logits)

    table = tf.lookup.StaticHashTable(
        tf.lookup.KeyValueTensorInitializer(
            tf.constant(list(range(CLASSES)), tf.int64),
            tf.constant([b"neg", b"neu", b"pos", b"mix"])),
        default_value=b"UNK")
    ranked = tf.argsort(logits, direction="DESCENDING")
    classes = table.lookup(tf.cast(ranked, tf.int64))

    sig = tf1.saved_model.classification_signature_def(
        examples=serialized, classes=classes, scores=scores)
    builder = tf1.saved_model.Builder(export_dir)
    with tf1.Session() as sess:
        sess.run(tf1.global_variables_initializer())
        sess.run(tf1.tables_initializer())
        builder.add_meta_graph_and_variables(
            sess, [tf1.saved_model.SERVING],
            signature_def_map={"serving_default": sig},
            main_op=tf1.tables_initializer())
        builder.save()
        got_scores, got_classes = sess.run(
            [scores, classes], {serialized: list(payloads)})
np.savez(out_path, scores=got_scores, classes=got_classes)
print("SAVED")
"""


def _run_tf(script, *args):
    return subprocess.run(
        [sys.executable, "-c", script, *args], capture_output=True,
        text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin:/usr/local/bin",
             "CUDA_VISIBLE_DEVICES": "-1", "JAX_PLATFORMS": "cpu",
             "TF_CPP_MIN_LOG_LEVEL": "3", "HOME": "/root"})


FEATURES = [
    {"ids": np.array([1, 5, 9, 2, 0, 31], np.int64)},
    {"ids": np.array([3, 3, 8, 30, 12, 7], np.int64)},
    {"ids": np.array([0, 1, 2, 3, 4, 5], np.int64)},
]


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("partition_export")
    payloads = np.array(
        [example_from_dict(d).SerializeToString() for d in FEATURES],
        dtype=object)
    ex_path = tmp / "examples.npy"
    np.save(ex_path, payloads, allow_pickle=True)
    version_dir = tmp / "model" / "1"
    out_path = tmp / "tf_out.npz"
    proc = _run_tf(EXPORT_SCRIPT, str(version_dir), str(ex_path),
                   str(out_path))
    if "SAVED" not in proc.stdout:
        pytest.skip(f"tensorflow unavailable: {proc.stderr[-500:]}")
    return version_dir, np.load(out_path, allow_pickle=True)


@pytest.mark.integration
def test_interior_is_device_jitted(exported):
    version_dir, _ = exported
    servable = load_saved_model(str(version_dir), "tfm", 1)
    sig = servable.signature("")
    assert sig.on_host  # the label table keeps the WRAPPER host-side
    part = sig.partition
    assert part is not None, "transformer classify export must partition"
    # The lookup is host-post; the MXU work is in the interior.
    assert "LookupTableFindV2" in part.stats["host_post_ops"]
    interior = set(part.stats["interior_ops"])
    assert interior & {"MatMul", "BatchMatMulV2", "Einsum"}, interior
    assert "LookupTableFindV2" not in interior

    # The interior really traces to device ops: its jaxpr carries the
    # dot_generals of the attention block, not numpy calls.
    from min_tfs_client_tpu.tensor.example_codec import decode_examples

    feats = decode_examples([example_from_dict(d) for d in FEATURES],
                            sig.feature_specs)
    # No host-pre stage here (the parsed ids are dense): the interior's
    # feeds are exactly the signature's feeds.
    assert part.cut_in_refs == []
    jaxpr = part.interior_jaxpr_text([np.asarray(feats["ids"])])
    assert "dot_general" in jaxpr


@pytest.mark.integration
def test_partitioned_numerics_match_tf(exported):
    version_dir, want = exported
    servable = load_saved_model(str(version_dir), "tfm", 1)
    sig = servable.signature("")
    from min_tfs_client_tpu.tensor.example_codec import decode_examples

    feats = decode_examples([example_from_dict(d) for d in FEATURES],
                            sig.feature_specs)
    out = sig.run(feats)
    np.testing.assert_allclose(out["scores"], want["scores"],
                               rtol=1e-4, atol=1e-5)
    got_classes = np.vectorize(
        lambda b: b if isinstance(b, bytes) else bytes(b))(out["classes"])
    np.testing.assert_array_equal(got_classes, want["classes"])


@pytest.mark.integration
def test_partitioned_serves_classify_end_to_end(exported):
    version_dir, want = exported
    srv = Server(ServerOptions(
        grpc_port=0, model_name="tfm",
        model_base_path=str(version_dir.parent),
        file_system_poll_wait_seconds=0)).build_and_start()
    try:
        with TensorServingClient("127.0.0.1", srv.grpc_port) as client:
            resp = client.classification_request("tfm", FEATURES,
                                                 timeout=120)
            result = resp.result
            assert len(result.classifications) == len(FEATURES)
            for i, cl in enumerate(result.classifications):
                np.testing.assert_allclose(
                    [c.score for c in cl.classes], want["scores"][i],
                    rtol=1e-4, atol=1e-5)
                assert [c.label for c in cl.classes] == [
                    lb.decode() for lb in want["classes"][i]]
    finally:
        srv.stop()


@pytest.mark.integration
def test_partitioned_interior_serves_dp_sharded_on_the_mesh(exported):
    """Round-6 tentpole: the SAME TF-cross-validated transformer export
    serves through ServerCore with a server-level mesh — the partitioned
    interior runs batch-DP-sharded over all 8 virtual devices (sharding
    asserted in the lowered interior HLO) and numerics stay TF-exact."""
    version_dir, want = exported
    from min_tfs_client_tpu.core.server_core import (
        ServerCore,
        single_model_config,
    )
    from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
    from min_tfs_client_tpu.protos import tfs_config_pb2
    from min_tfs_client_tpu.server.handlers import Handlers

    core = ServerCore(
        single_model_config("tfm", str(version_dir.parent),
                            platform="tensorflow"),
        file_system_poll_wait_seconds=0.05,
        platform_configs={"tensorflow": {
            "mesh_axes": {"data": 8},
            "batching_parameters": tfs_config_pb2.BatchingParameters(),
            "enable_model_warmup": False}})
    try:
        handlers = Handlers(core)
        req = apis.ClassificationRequest()
        req.model_spec.name = "tfm"
        for feats in FEATURES:
            ex = req.input.example_list.examples.add()
            ex.features.feature["ids"].int64_list.value.extend(
                feats["ids"].tolist())
        resp = handlers.classify(req)
        result = resp.result
        assert len(result.classifications) == len(FEATURES)
        for i, cl in enumerate(result.classifications):
            np.testing.assert_allclose(
                [c.score for c in cl.classes], want["scores"][i],
                rtol=1e-4, atol=1e-5)
            assert [c.label for c in cl.classes] == [
                lb.decode() for lb in want["classes"][i]]

        spec = apis.ModelSpec()
        spec.name = "tfm"
        with core.servable_handle(spec) as handle:
            sig = handle.servable.signature("")
            part = sig.partition
            assert part is not None
            assert part.mesh is not None
            assert dict(part.mesh.shape) == {"data": 8}
            # Batching front-end agrees with the divisible padding.
            assert sig.round_up_batch(3) % 8 == 0
            # The DP sharding reaches XLA: batch dim split over the 8
            # devices in the lowered interior HLO.
            ids = np.stack([f["ids"] for f in FEATURES] * 3)[:8]
            hlo = part.interior_hlo_text([ids])
            assert 'sdy.mesh @mesh = <["data"=8]>' in hlo
            assert '#sdy.sharding<@mesh, [{"data"}, {}]>' in hlo
    finally:
        core.stop()


@pytest.mark.integration
def test_two_tower_import_serves_both_towers_jitted():
    """dense -> vocab lookup -> dense (the two-tower ranker shape,
    VERDICT r5 Missing #3): BOTH towers must run as jitted device
    segments around the host island, end to end through ServerCore,
    numerics exact vs the all-host interpreter — with and without the
    mesh."""
    import pathlib
    import tempfile

    from tests import fixtures
    from min_tfs_client_tpu.core.server_core import (
        ServerCore,
        single_model_config,
    )
    from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
    from min_tfs_client_tpu.protos import tfs_config_pb2
    from min_tfs_client_tpu.server.handlers import Handlers
    from min_tfs_client_tpu.servables.graphdef_import import (
        GraphFunction,
        load_saved_model,
    )
    from min_tfs_client_tpu.tensor.codec import (
        ndarray_to_tensor_proto,
        tensor_proto_to_ndarray,
    )

    width = 8
    base = pathlib.Path(tempfile.mkdtemp()) / "two_tower"
    fixtures.write_imported_two_tower(base, width=width)

    # All-host oracle straight off the import (partition bypassed).
    oracle_sv = load_saved_model(str(base / "1"), "oracle", 1)
    oracle_part = oracle_sv.signature("").partition
    assert oracle_part is not None
    gf = GraphFunction(
        oracle_part._build_refs["graph_def"], ["x:0"],
        ["scores:0", "tower_a:0"],
        variables=oracle_part._build_refs["variables"],
        funclib=oracle_part._build_refs["funclib"],
        tables=oracle_part._build_refs["tables"])

    rng = np.random.default_rng(5)
    x = rng.standard_normal((5, width)).astype(np.float32)
    want_scores, want_tower = gf([x], np)

    core = ServerCore(
        single_model_config("two_tower", str(base), platform="tensorflow"),
        file_system_poll_wait_seconds=0.05,
        platform_configs={"tensorflow": {
            "mesh_axes": {"data": 8},
            "batching_parameters": tfs_config_pb2.BatchingParameters(),
            "enable_model_warmup": False}})
    try:
        handlers = Handlers(core)
        req = apis.PredictRequest()
        req.model_spec.name = "two_tower"
        req.inputs["x"].CopyFrom(ndarray_to_tensor_proto(x))
        resp = handlers.predict(req)
        got_scores = tensor_proto_to_ndarray(resp.outputs["scores"])
        got_tower = tensor_proto_to_ndarray(resp.outputs["tower_a"])
        np.testing.assert_allclose(got_scores, want_scores,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got_tower, want_tower,
                                   rtol=1e-5, atol=1e-6)

        spec = apis.ModelSpec()
        spec.name = "two_tower"
        with core.servable_handle(spec) as handle:
            part = handle.servable.signature("").partition
            assert part is not None
            assert part.stats["n_segments"] == 2
            assert part.mesh is not None
            # Both towers trace to device dots.
            probe = np.ones((8, width), np.float32)
            assert "dot_general" in part.interior_jaxpr_text(
                [probe], seg_idx=0)
            # Segment 1's interior feeds are its cuts (lookup + tower A).
            cut_vals = [
                np.arange(8, dtype=np.int64) % width,
                probe,
            ]
            assert "dot_general" in part.interior_jaxpr_text(
                cut_vals, seg_idx=1)
            assert "LookupTableFindV2" in part.stats["host_mid_ops"]
    finally:
        core.stop()


@pytest.mark.integration
def test_windowed_serving_bit_identical_through_server_core(exported):
    """ISSUE 5: the SAME TF-cross-validated classify export served
    through ServerCore with the in-flight execution window
    (max_in_flight_batches=4) under concurrent load must return
    BIT-identical responses to the window=1 (serial) core — the window
    overlaps wall-clock, never values — and the window must thread all
    the way through: batching runner depth 4, partition microbatch
    pipeline depth 4."""
    import concurrent.futures as cf

    version_dir, _ = exported
    from min_tfs_client_tpu.core.server_core import (
        ServerCore,
        single_model_config,
    )
    from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
    from min_tfs_client_tpu.protos import tfs_config_pb2
    from min_tfs_client_tpu.server.handlers import Handlers

    rng = np.random.default_rng(11)
    requests = []
    for _ in range(24):
        req = apis.ClassificationRequest()
        req.model_spec.name = "tfm"
        for _ in range(2):
            ex = req.input.example_list.examples.add()
            ex.features.feature["ids"].int64_list.value.extend(
                rng.integers(0, 32, size=6).tolist())
        requests.append(req)

    def serve(window):
        config = {"batching_parameters":
                  tfs_config_pb2.BatchingParameters(),
                  "enable_model_warmup": False}
        if window > 1:
            config["max_in_flight_batches"] = window
        core = ServerCore(
            single_model_config("tfm", str(version_dir.parent),
                                platform="tensorflow"),
            file_system_poll_wait_seconds=0.05,
            platform_configs={"tensorflow": config})
        try:
            handlers = Handlers(core)
            with cf.ThreadPoolExecutor(8) as pool:
                responses = list(pool.map(handlers.classify, requests))
            spec = apis.ModelSpec()
            spec.name = "tfm"
            with core.servable_handle(spec) as handle:
                sig = handle.servable.signature("")
                part = sig.partition
                assert part is not None
                assert part.pipeline_depth == max(1, window)
            return [
                [([c.score for c in cl.classes],
                  [c.label for c in cl.classes])
                 for cl in resp.result.classifications]
                for resp in responses]
        finally:
            core.stop()

    serial = serve(1)
    windowed = serve(4)
    assert len(serial) == len(windowed) == len(requests)
    for s_resp, w_resp in zip(serial, windowed):
        assert len(s_resp) == len(w_resp)
        for (s_scores, s_labels), (w_scores, w_labels) in zip(s_resp,
                                                              w_resp):
            assert s_scores == w_scores  # bit-identical, not allclose
            assert s_labels == w_labels
