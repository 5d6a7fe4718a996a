"""Unit tests for the host/device graph partitioner on hand-built
GraphDefs (no TF, no SavedModel): stage classification, cut tensors,
batch-bucket padding, and the fallback rules."""

from __future__ import annotations

import numpy as np
import pytest

from min_tfs_client_tpu.protos import tf_graph_pb2
from min_tfs_client_tpu.servables.graphdef_import import (
    GraphFunction,
    LookupTable,
    _FuncLib,
)
from min_tfs_client_tpu.servables.partition import try_partition
from min_tfs_client_tpu.tensor.codec import ndarray_to_tensor_proto

DT_FLOAT, DT_STRING, DT_INT64, DT_INT32 = 1, 7, 9, 3


def _const(gd, name, arr):
    node = gd.node.add()
    node.name = name
    node.op = "Const"
    node.attr["value"].tensor.CopyFrom(ndarray_to_tensor_proto(arr))
    return node


def _classify_graph():
    """x -> MatMul(w) -> Softmax -> ArgMax -> table lookup (string).

    The canonical classify-with-labels shape: dense interior + host
    label lookup at the end.
    """
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "x"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_FLOAT
    _const(gd, "w", np.arange(12, dtype=np.float32).reshape(3, 4) * 0.1)
    mm = gd.node.add()
    mm.name = "logits"
    mm.op = "MatMul"
    mm.input.extend(["x", "w"])
    sm = gd.node.add()
    sm.name = "scores"
    sm.op = "Softmax"
    sm.input.append("logits")
    _const(gd, "axis", np.asarray(1, np.int32))
    am = gd.node.add()
    am.name = "best"
    am.op = "ArgMax"
    am.input.extend(["logits", "axis"])
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_INT64
    table.attr["value_dtype"].type = DT_STRING
    _const(gd, "default", np.asarray(b"UNK", object))
    find = gd.node.add()
    find.name = "label"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "best", "default"])
    return gd


def _tables():
    return {"tbl": LookupTable([0, 1, 2, 3],
                               [b"a", b"b", b"c", b"d"], True)}


def test_classify_graph_partitions():
    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    assert "MatMul" in part.stats["interior_ops"]
    assert "LookupTableFindV2" in part.stats["host_post_ops"]
    assert part.cut_in_refs == []
    # ArgMax is numeric -> interior; its output is the host cut.
    assert set(part.interior_out_refs) >= {"scores:0", "best:0"}

    x = np.array([[1.0, 0.0, 2.0], [0.5, 0.5, 0.5], [0.0, 3.0, 1.0]],
                 np.float32)
    outs = part.run([x], batch_buckets=(4, 8))
    ref_fn = GraphFunction(gd, ["x:0"], ["scores:0", "label:0"],
                           tables=_tables())
    want = ref_fn([x], np)
    np.testing.assert_allclose(outs[0], want[0], rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(outs[1], object), want[1])


def test_padding_rounds_to_bucket_and_slices_back():
    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    x = np.ones((3, 3), np.float32)
    outs = part.run([x], batch_buckets=(8,))
    assert np.asarray(outs[0]).shape == (3, 4)
    assert np.asarray(outs[1]).shape == (3,)


def test_pure_device_graph_returns_none():
    # Fetching only the dense outputs: no host node reachable, nothing
    # to split — the regular jitted device path already covers it.
    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is None


def test_jaxpr_shows_device_dots():
    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    text = part.interior_jaxpr_text([np.ones((2, 3), np.float32)])
    assert "dot_general" in text


def test_no_flops_returns_none():
    # Lookup-only graph: nothing for the MXU, partition refuses.
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "ids"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_INT64
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    _const(gd, "default", np.asarray(b"UNK", object))
    find = gd.node.add()
    find.name = "label"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "ids", "default"])
    part = try_partition(gd, ["ids:0"], ["label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is None


def _string_cut_graph():
    """string feed -> host lookup (int values) -> MatMul: the pre stage
    computes the cut, the interior consumes ONLY cuts (no direct feed)."""
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "tok"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_STRING
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_STRING
    table.attr["value_dtype"].type = DT_INT64
    _const(gd, "default", np.asarray(0, np.int64))
    find = gd.node.add()
    find.name = "ids"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "tok", "default"])
    cast = gd.node.add()
    cast.name = "idsf"
    cast.op = "Cast"
    cast.input.append("ids")
    cast.attr["SrcT"].type = DT_INT64
    cast.attr["DstT"].type = DT_FLOAT
    _const(gd, "w", np.eye(2, dtype=np.float32))
    mm = gd.node.add()
    mm.name = "out"
    mm.op = "MatMul"
    mm.input.extend(["idsf", "w"])
    tables = {"tbl": LookupTable([b"x", b"y"], [3, 5], False)}
    return gd, tables


def test_host_pre_cut_feeds_interior():
    gd, tables = _string_cut_graph()
    part = try_partition(gd, ["tok:0"], ["out:0"],
                         funclib=_FuncLib(None), tables=tables,
                         string_feed_refs=frozenset(["tok:0"]))
    assert part is not None
    assert part.cut_in_refs == ["ids:0"]
    assert "LookupTableFindV2" in part.stats["host_pre_ops"]
    tok = np.array([[b"x", b"y"], [b"y", b"y"]], object)
    outs = part.run([tok], batch_buckets=(2,))
    np.testing.assert_allclose(outs[0], [[3.0, 5.0], [5.0, 5.0]])


def test_alternating_host_device_host_device_jits_both_segments():
    """D -> H (int-valued lookup) -> D again: two device segments; the
    partitioner now jits BOTH (per-node placement, placer.h:55) instead
    of demoting one tower to numpy — numerics must match the all-host
    reference."""
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "x"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_FLOAT
    _const(gd, "w", np.eye(3, dtype=np.float32))
    mm = gd.node.add()
    mm.name = "h1"
    mm.op = "MatMul"
    mm.input.extend(["x", "w"])
    _const(gd, "axis", np.asarray(1, np.int32))
    am = gd.node.add()
    am.name = "best"
    am.op = "ArgMax"
    am.input.extend(["h1", "axis"])
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_INT64
    table.attr["value_dtype"].type = DT_INT64
    _const(gd, "default", np.asarray(0, np.int64))
    find = gd.node.add()
    find.name = "mapped"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "best", "default"])
    cast = gd.node.add()
    cast.name = "mf"
    cast.op = "Cast"
    cast.input.append("mapped")
    cast.attr["SrcT"].type = DT_INT64
    cast.attr["DstT"].type = DT_FLOAT
    oh = gd.node.add()
    oh.name = "mf2"
    oh.op = "ExpandDims"
    oh.input.extend(["mf", "axis"])
    _const(gd, "w2", np.asarray([[1.0, 2.0, 3.0]], np.float32))
    mm2 = gd.node.add()
    mm2.name = "h2"
    mm2.op = "MatMul"
    mm2.input.extend(["mf2", "w2"])
    tables = {"tbl": LookupTable([0, 1, 2], [7, 8, 9], False)}
    part = try_partition(gd, ["x:0"], ["h2:0"],
                         funclib=_FuncLib(None), tables=tables)
    assert part is not None
    assert part.stats["n_segments"] == 2
    assert part.stats["segments"] == [0, 2]
    assert "MatMul" in part.stats["interior_ops"]
    # NO MatMul left on host: both towers jitted, only the int lookup
    # stays on numpy (a host island between the segments).
    assert "MatMul" not in part.stats["host_pre_ops"]
    assert "MatMul" not in part.stats["host_mid_ops"]
    assert "MatMul" not in part.stats["host_post_ops"]
    assert "LookupTableFindV2" in part.stats["host_mid_ops"]
    # The second tower consumes the lookup through a cut tensor.
    assert part.segments[1].cut_in_refs == ["mapped:0"]
    x = np.array([[0.1, 2.0, 0.3]], np.float32)
    outs = part.run([x], batch_buckets=(1, 2))
    ref = GraphFunction(gd, ["x:0"], ["h2:0"], tables=tables)
    np.testing.assert_allclose(outs[0], ref([x], np)[0], rtol=1e-6)


def test_multi_slot_fed_node_uses_only_consumed_slots():
    """Feeds sharing one node name (the ParseExample bypass shape): the
    interior must take ONLY the slot it consumes as a jit argument — a
    string sibling slot fed to a host lookup must not leak in."""
    gd = tf_pb2 = tf_graph_pb2.GraphDef()
    # "parse" stands in for a bypassed multi-output node: both feeds are
    # slots of it (never evaluated — fed), so no op/attrs needed.
    parse = gd.node.add()
    parse.name = "parse"
    parse.op = "Placeholder"
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_STRING
    table.attr["value_dtype"].type = DT_STRING
    _const(gd, "default", np.asarray(b"UNK", object))
    find = gd.node.add()
    find.name = "label"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "parse:1", "default"])
    _const(gd, "w", np.eye(2, dtype=np.float32))
    mm = gd.node.add()
    mm.name = "logits"
    mm.op = "MatMul"
    mm.input.extend(["parse:0", "w"])
    tables = {"tbl": LookupTable([b"x"], [b"X"], True)}
    part = try_partition(
        gd, ["parse:0", "parse:1"], ["logits:0", "label:0"],
        funclib=_FuncLib(None), tables=tables,
        string_feed_refs=frozenset(["parse:1"]))
    assert part is not None
    assert part.used_feed_idx == [0]  # slot 0 only, not the string slot
    x = np.array([[1.0, 2.0]], np.float32)
    toks = np.array([b"x"], object)
    outs = part.run([x, toks], batch_buckets=(1, 2))
    np.testing.assert_allclose(outs[0], x)
    np.testing.assert_array_equal(np.asarray(outs[1], object), [b"X"])


def test_fixed_size_output_not_truncated_by_bucket_padding():
    """A fixed-size fetch (vocab-style Const passthrough) whose length
    equals the padding bucket must NOT be sliced to the true batch —
    the batch-1 calibration learns which outputs are batch-major."""
    gd = _classify_graph()
    # Fixed fetch of length 4 == the bucket used below.
    _const(gd, "vocab", np.arange(4, dtype=np.float32))
    vid = gd.node.add()
    vid.name = "vocab_out"
    vid.op = "Identity"
    vid.input.append("vocab")
    vid.attr["T"].type = DT_FLOAT
    part = try_partition(gd, ["x:0"],
                         ["scores:0", "label:0", "vocab_out:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    x = np.ones((3, 3), np.float32)  # batch 3 -> bucket 4
    outs = part.run([x], batch_buckets=(4,))
    assert np.asarray(outs[0]).shape == (3, 4)   # batch-major: sliced
    assert np.asarray(outs[2]).shape == (4,)     # fixed: NOT sliced
    np.testing.assert_allclose(outs[2], [0.0, 1.0, 2.0, 3.0])


def test_imported_transformer_fixture_partitions_and_serves():
    """The no-TF transformer classify fixture (tests/fixtures.py) used
    by the bench 'imported' leg and the on-device tier: must import,
    partition (jitted interior with the attention matmuls), and serve
    ranked labels deterministically."""
    import tempfile
    import pathlib

    from tests import fixtures
    from min_tfs_client_tpu.servables.graphdef_import import (
        load_saved_model,
    )
    from min_tfs_client_tpu.tensor.example_codec import (
        decode_examples,
        example_from_dict,
    )

    base = pathlib.Path(tempfile.mkdtemp()) / "imported"
    fixtures.write_imported_transformer_classify(
        base, seq=16, d_model=32, layers=1, vocab=128, labels=4)
    servable = load_saved_model(str(base / "1"), "imported", 1)
    sig = servable.signature("")
    assert sig.method_name == "tensorflow/serving/classify"
    assert sig.partition is not None
    assert "BatchMatMulV2" in sig.partition.stats["interior_ops"]
    assert "LookupTableFindV2" in sig.partition.stats["host_post_ops"]

    rng = np.random.default_rng(1)
    feats = [{"ids": rng.integers(0, 128, 16)} for _ in range(3)]
    dec = decode_examples([example_from_dict(f) for f in feats],
                          sig.feature_specs)
    out = sig.run(dec)
    classes = np.asarray(out["classes"], object)
    scores = np.asarray(out["scores"])
    assert classes.shape == (3, 4) and scores.shape == (3, 4)
    assert all(bytes(c).startswith(b"class_")
               for c in classes.reshape(-1))
    # Ranked: scores descending per example.
    assert (np.diff(scores, axis=1) <= 1e-6).all()
    out2 = sig.run(dec)
    np.testing.assert_array_equal(scores, np.asarray(out2["scores"]))


def test_runtime_partition_error_falls_back_to_host(monkeypatch):
    """A PartitionError at serve time (e.g. a shape operand that turns
    out to be unspecializable) must fall back to the always-correct
    all-host path, not fail the request (graphdef_import.make_part_fn)."""
    import pathlib
    import tempfile

    from tests import fixtures
    from min_tfs_client_tpu.servables import partition as part_mod
    from min_tfs_client_tpu.servables.graphdef_import import (
        load_saved_model,
    )
    from min_tfs_client_tpu.tensor.example_codec import (
        decode_examples,
        example_from_dict,
    )

    base = pathlib.Path(tempfile.mkdtemp()) / "imported"
    fixtures.write_imported_transformer_classify(
        base, seq=8, d_model=16, layers=1, vocab=32, labels=4)
    servable = load_saved_model(str(base / "1"), "imported", 1)
    sig = servable.signature("")
    assert sig.partition is not None

    def boom(self, feed_values, batch_buckets):
        raise part_mod.PartitionError("forced for test")

    monkeypatch.setattr(part_mod.GraphPartition, "run", boom)
    feats = [{"ids": np.arange(8, dtype=np.int64) % 32}]
    dec = decode_examples([example_from_dict(f) for f in feats],
                          sig.feature_specs)
    out = sig.run(dec)  # host fallback, not an error
    assert np.asarray(out["classes"]).shape == (1, 4)
    assert np.isclose(np.asarray(out["scores"]).sum(), 1.0, atol=1e-4)


def test_cut_lists_deterministic_across_hash_seeds():
    """interior_out_refs / cut_in_refs / stats must not depend on set
    iteration order (hash randomization): two processes with different
    PYTHONHASHSEED must produce identical partitions, or partition
    stats, stage fetch order, and jit cache keys diverge across
    processes (ADVICE r5 low)."""
    import json
    import os
    import subprocess
    import sys

    code = """
import json
import numpy as np
from tests.unit.test_partition import _classify_graph, _tables
from min_tfs_client_tpu.servables.graphdef_import import _FuncLib
from min_tfs_client_tpu.servables.partition import try_partition

gd = _classify_graph()
# Extra fetches widen the consumer set so ordering differences would show.
part = try_partition(gd, ["x:0"], ["scores:0", "label:0", "best:0"],
                     funclib=_FuncLib(None), tables=_tables())
print(json.dumps({
    "cut_in": part.cut_in_refs,
    "interior_out": part.interior_out_refs,
    "used_feed_idx": part.used_feed_idx,
    "stats": part.stats,
}, sort_keys=True))
"""
    outs = []
    for seed in ("0", "1", "42"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        res = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))), env=env)
        assert res.returncode == 0, res.stderr[-2000:]
        outs.append(json.loads(res.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1] == outs[2]


def test_calibration_failure_is_recorded_not_silent():
    """A failing batch-1 calibration probe keeps the dim-match heuristic
    but must RECORD the failure (metric + log) instead of passing
    silently (ADVICE r5: a bare except here can hide truncation of
    fixed-size outputs that coincide with the padding bucket)."""
    from min_tfs_client_tpu.server import metrics

    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None

    def boom(*a, **k):
        raise RuntimeError("forced probe failure")

    part.interior_jitted = boom
    before = metrics.partition_calibration_failures.value("unknown")
    part._calibrate([np.ones((3, 3), np.float32)])
    assert part._interior_batch_major is None  # heuristic retained
    after = metrics.partition_calibration_failures.value("unknown")
    assert after == before + 1


def test_calibration_probe_slices_only_batch_major_feeds():
    """The batch-1 probe must slice exactly the feeds sharing the batch
    dim (the _pad_interior criterion) — slicing a fixed-size side feed
    would probe the graph with a semantically wrong input."""
    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    seen = []
    real_jitted = part.interior_jitted

    def spy(stat, key):
        fn = real_jitted(stat, key)

        def wrapped(dyn):
            seen.append([np.asarray(v).shape for v in dyn])
            return fn(dyn)

        return wrapped

    part.interior_jitted = spy
    # Feeds share batch dim 3 -> the probe slices to 1 row.
    part._calibrate([np.ones((3, 3), np.float32)])
    assert part._interior_batch_major is not None
    assert seen and seen[0][0][0] == 1


def test_calibration_ambiguous_batch_dims_is_a_recorded_failure():
    """INTERIOR feeds that disagree on the leading dim leave the probe
    with no batch reference: it must record a calibration failure and
    keep the heuristic, never probe at full batch and learn wrong
    flags. (A host-only side feed of a different length is fine — the
    criterion runs over the interior-consumed feeds, like
    _pad_interior.)"""
    from min_tfs_client_tpu.server import metrics

    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    part.used_feed_idx = [0, 1]  # two interior feeds with mixed dims
    part.static_flags = [False, False]
    before = metrics.partition_calibration_failures.value("unknown")
    part._calibrate([np.ones((3, 3), np.float32),
                     np.ones((7,), np.float32)])
    assert part._interior_batch_major is None
    assert part._result_batch_major is None
    assert part._calibration_failed
    assert metrics.partition_calibration_failures.value("unknown") \
        == before + 1


def test_calibration_ignores_host_only_side_feed_dims():
    """A feed the interior does not consume (a host-only side input of a
    different length) must neither block calibration nor be sliced: the
    batch reference comes from the interior-consumed feeds only, like
    _pad_interior's padding decision."""
    gd = _classify_graph()
    side = gd.node.add()
    side.name = "side"
    side.op = "Placeholder"
    side.attr["dtype"].type = DT_INT64
    find = gd.node.add()
    find.name = "side_label"   # host-only consumer; side never reaches
    find.op = "LookupTableFindV2"  # the jitted interior
    find.input.extend(["tbl", "side", "default"])
    part = try_partition(gd, ["x:0", "side:0"],
                         ["scores:0", "label:0", "side_label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    assert part.used_feed_idx == [0]  # interior consumes only x
    x = np.ones((3, 3), np.float32)       # batch 3 -> bucket 4
    side_v = np.arange(7, dtype=np.int64)    # length != batch
    outs = part.run([x, side_v], batch_buckets=(4,))
    assert part._interior_batch_major is not None  # calibration ran
    assert not part._calibration_failed
    assert np.asarray(outs[0]).shape == (3, 4)  # sliced back
    assert np.asarray(outs[2]).shape == (7,)    # side output untouched


def test_calibration_failure_latches_and_records_once(scheduler=None):
    """A persistently failing probe is recorded ONCE: later padded
    requests keep the heuristic without re-probing, re-logging, or
    re-incrementing the failure counter per request."""
    from min_tfs_client_tpu.server import metrics

    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    real_jitted = part.interior_jitted

    def probe_poison(stat, key):
        fn = real_jitted(stat, key)

        def wrapped(dyn):
            if np.asarray(dyn[0]).shape[0] == 1:  # the batch-1 probe
                raise RuntimeError("forced probe failure")
            return fn(dyn)

        return wrapped

    part.interior_jitted = probe_poison
    before = metrics.partition_calibration_failures.value("unknown")
    x = np.ones((3, 3), np.float32)  # batch 3 -> bucket 4: sliced path
    for _ in range(3):
        outs = part.run([x], batch_buckets=(4,))
        assert np.asarray(outs[0]).shape == (3, 4)  # heuristic slicing
    assert metrics.partition_calibration_failures.value("unknown") \
        == before + 1  # once, despite three padded requests


def test_calibration_with_cut_only_interior_uses_cut_dims():
    """When the interior consumes ONLY cut tensors (string-feed graphs:
    used_feed_idx is empty), the calibration batch reference must come
    from the cuts _pad_interior actually pads — not from all signature
    feeds — so the probe still calibrates instead of latching failure."""
    gd, tables = _string_cut_graph()
    part = try_partition(gd, ["tok:0"], ["out:0"],
                         funclib=_FuncLib(None), tables=tables,
                         string_feed_refs=frozenset(["tok:0"]))
    assert part is not None
    assert part.used_feed_idx == []
    tok = np.array([[b"x", b"y"], [b"y", b"y"], [b"x", b"x"]], object)
    outs = part.run([tok], batch_buckets=(4,))  # batch 3 -> bucket 4
    assert not part._calibration_failed
    assert part._interior_batch_major is not None  # probe succeeded
    np.testing.assert_allclose(
        outs[0], [[3.0, 5.0], [5.0, 5.0], [3.0, 3.0]])


def test_calibration_refuses_full_batch_probe():
    """If slicing the signature feeds does not propagate to the interior
    inputs (e.g. a pre stage that reshapes the batch away), the probe
    must fail loudly and keep the heuristic — never learn batch-major
    flags from a full-batch run (outputs' leading dim != 1 would mark
    every batch-major output as fixed, leaking padded rows)."""
    from min_tfs_client_tpu.server import metrics

    gd, tables = _string_cut_graph()
    part = try_partition(gd, ["tok:0"], ["out:0"],
                         funclib=_FuncLib(None), tables=tables,
                         string_feed_refs=frozenset(["tok:0"]))
    assert part is not None
    tok = np.array([[b"x", b"y"], [b"y", b"y"], [b"x", b"x"]], object)
    real_pre = part.pre
    part.pre = lambda feeds, lib: real_pre([tok], lib)  # ignores slicing
    before = metrics.partition_calibration_failures.value("unknown")
    part._calibrate([tok])
    assert part._interior_batch_major is None
    assert part._calibration_failed
    assert metrics.partition_calibration_failures.value("unknown") \
        == before + 1


# -- multi-segment, FLOP weighting, and mesh sharding (round 6) --------------


def _two_tower_graph():
    """Dense tower A -> int vocab lookup (host island) -> dense tower B:
    the shape that used to leave one tower on numpy (VERDICT r5 Missing
    #3). Tower B mixes the lookup back into tower A's activations, so
    its cut set carries BOTH a host value and an earlier interior's
    output."""
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "x"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_FLOAT
    _const(gd, "wa", (np.arange(16, dtype=np.float32).reshape(4, 4) * 0.1))
    mm = gd.node.add()
    mm.name = "h1"
    mm.op = "MatMul"
    mm.input.extend(["x", "wa"])
    r1 = gd.node.add()
    r1.name = "r1"
    r1.op = "Relu"
    r1.input.append("h1")
    _const(gd, "axis", np.asarray(1, np.int32))
    am = gd.node.add()
    am.name = "best"
    am.op = "ArgMax"
    am.input.extend(["r1", "axis"])
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_INT64
    table.attr["value_dtype"].type = DT_INT64
    _const(gd, "default", np.asarray(0, np.int64))
    find = gd.node.add()
    find.name = "mapped"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "best", "default"])
    cast = gd.node.add()
    cast.name = "mf"
    cast.op = "Cast"
    cast.input.append("mapped")
    cast.attr["SrcT"].type = DT_INT64
    cast.attr["DstT"].type = DT_FLOAT
    col = gd.node.add()
    col.name = "col"
    col.op = "ExpandDims"
    col.input.extend(["mf", "axis"])
    mix = gd.node.add()
    mix.name = "mix"
    mix.op = "Mul"
    mix.input.extend(["r1", "col"])
    _const(gd, "wb", (np.arange(16, dtype=np.float32).reshape(4, 4) * 0.05))
    mm2 = gd.node.add()
    mm2.name = "h2"
    mm2.op = "MatMul"
    mm2.input.extend(["mix", "wb"])
    sm = gd.node.add()
    sm.name = "scores"
    sm.op = "Softmax"
    sm.input.append("h2")
    tables = {"tbl": LookupTable([0, 1, 2, 3], [5, 6, 7, 8], False)}
    return gd, tables


def test_two_tower_serves_both_towers_jitted():
    gd, tables = _two_tower_graph()
    part = try_partition(gd, ["x:0"], ["scores:0"],
                         funclib=_FuncLib(None), tables=tables)
    assert part is not None
    assert part.stats["n_segments"] == 2
    # Tower B's cuts: the host lookup AND tower A's activation (an
    # earlier interior's output rides the same ledger as host cuts).
    assert "mapped:0" in part.segments[1].cut_in_refs
    assert "r1:0" in part.segments[1].cut_in_refs
    assert "MatMul" not in part.stats["host_pre_ops"]
    assert "MatMul" not in part.stats["host_mid_ops"]
    # Both towers trace to device dots.
    x = np.random.default_rng(0).standard_normal((2, 4)).astype(np.float32)
    assert "dot_general" in part.interior_jaxpr_text([x], seg_idx=0)
    ref = GraphFunction(gd, ["x:0"], ["scores:0"], tables=tables)
    for batch in (1, 3, 5):
        xb = np.random.default_rng(batch).standard_normal(
            (batch, 4)).astype(np.float32)
        outs = part.run([xb], batch_buckets=(4, 8))
        np.testing.assert_allclose(outs[0], ref([xb], np)[0],
                                   rtol=1e-5, atol=1e-6)


def test_conv_graph_with_string_labels_partitions():
    """A conv-only interior with a string label lookup used to count
    ZERO MXU ops when the op wasn't in FLOP_OPS and silently stayed
    all-host (VERDICT r5 Weak #5); Conv2D carries weighted FLOPs now."""
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "images"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_FLOAT
    _const(gd, "filt",
           (np.random.default_rng(0).standard_normal((2, 2, 1, 3)) * 0.3
            ).astype(np.float32))
    conv = gd.node.add()
    conv.name = "conv"
    conv.op = "Conv2D"
    conv.input.extend(["images", "filt"])
    conv.attr["strides"].list.i.extend([1, 1, 1, 1])
    conv.attr["padding"].s = b"SAME"
    _const(gd, "axes", np.asarray([1, 2], np.int32))
    pool = gd.node.add()
    pool.name = "pool"
    pool.op = "Mean"
    pool.input.extend(["conv", "axes"])
    _const(gd, "axis1", np.asarray(1, np.int32))
    am = gd.node.add()
    am.name = "best"
    am.op = "ArgMax"
    am.input.extend(["pool", "axis1"])
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_INT64
    table.attr["value_dtype"].type = DT_STRING
    _const(gd, "default", np.asarray(b"UNK", object))
    find = gd.node.add()
    find.name = "label"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "best", "default"])
    tables = {"tbl": LookupTable([0, 1, 2], [b"a", b"b", b"c"], True)}
    part = try_partition(gd, ["images:0"], ["pool:0", "label:0"],
                         funclib=_FuncLib(None), tables=tables)
    assert part is not None, "conv interior must partition, not stay host"
    assert "Conv2D" in part.stats["interior_ops"]
    assert "LookupTableFindV2" in part.stats["host_post_ops"]
    x = np.random.default_rng(1).standard_normal(
        (3, 4, 4, 1)).astype(np.float32)
    outs = part.run([x], batch_buckets=(4,))
    ref = GraphFunction(gd, ["images:0"], ["pool:0", "label:0"],
                        tables=tables)
    want = ref([x], np)
    np.testing.assert_allclose(outs[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(outs[1], object), want[1])


def test_segment_choice_tracks_flops_not_op_count():
    """Two towers around a host island: three tiny 2x2 matmuls vs ONE
    64x64 matmul. Op counting would rank the tiny tower first; the
    weighted FLOP estimate must make the big matmul the primary segment
    (stats['segment'], the single-segment fallback choice)."""
    gd = tf_graph_pb2.GraphDef()
    ph = gd.node.add()
    ph.name = "x"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_FLOAT
    prev = "x"
    for i in range(3):  # tiny tower: 3 ops, 2x2 weights
        _const(gd, f"w{i}", np.eye(2, dtype=np.float32))
        mm = gd.node.add()
        mm.name = f"t{i}"
        mm.op = "MatMul"
        mm.input.extend([prev, f"w{i}"])
        prev = f"t{i}"
    _const(gd, "axis", np.asarray(1, np.int32))
    am = gd.node.add()
    am.name = "best"
    am.op = "ArgMax"
    am.input.extend([prev, "axis"])
    table = gd.node.add()
    table.name = "tbl"
    table.op = "HashTableV2"
    table.attr["key_dtype"].type = DT_INT64
    table.attr["value_dtype"].type = DT_INT64
    _const(gd, "default", np.asarray(0, np.int64))
    find = gd.node.add()
    find.name = "mapped"
    find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "best", "default"])
    cast = gd.node.add()
    cast.name = "mf"
    cast.op = "Cast"
    cast.input.append("mapped")
    cast.attr["SrcT"].type = DT_INT64
    cast.attr["DstT"].type = DT_FLOAT
    oh = gd.node.add()
    oh.name = "col"
    oh.op = "ExpandDims"
    oh.input.extend(["mf", "axis"])
    _const(gd, "big_w", np.ones((1, 64), np.float32))
    mm2 = gd.node.add()
    mm2.name = "big"   # one op, 64-wide weight: the real compute
    mm2.op = "MatMul"
    mm2.input.extend(["col", "big_w"])
    tables = {"tbl": LookupTable([0, 1], [3, 4], False)}
    part = try_partition(gd, ["x:0"], ["big:0"],
                         funclib=_FuncLib(None), tables=tables)
    assert part is not None
    assert part.stats["n_segments"] == 2
    flops = part.stats["segment_flops"]
    assert flops[str(part.segments[1].seg_value)] > \
        flops[str(part.segments[0].seg_value)]
    assert part.stats["segment"] == part.segments[1].seg_value


def test_attach_mesh_dp_shards_interior_and_matches_host():
    """8-device CPU mesh: the interior pads to a data-axis-divisible
    bucket, lands batch-DP-sharded (asserted in the lowered HLO), and
    numerics stay exact vs the all-host oracle."""
    from min_tfs_client_tpu.parallel.mesh import make_mesh

    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    mesh = make_mesh({"data": 8})
    part.attach_mesh(mesh)
    assert part.mesh is mesh
    x = np.random.default_rng(0).standard_normal((5, 3)).astype(np.float32)
    outs = part.run([x], batch_buckets=(4, 8, 16))  # 4 skipped: 5 -> 8
    ref = GraphFunction(gd, ["x:0"], ["scores:0", "label:0"],
                        tables=_tables())
    want = ref([x], np)
    assert np.asarray(outs[0]).shape == (5, 4)  # sliced back
    np.testing.assert_allclose(outs[0], want[0], rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(outs[1], object), want[1])
    # The DP sharding really reaches XLA: batch dim split over 8 devices
    # (Shardy's spelling — jax 0.9 no longer emits GSPMD's devices=[8,1]).
    hlo = part.interior_hlo_text([np.ones((8, 3), np.float32)])
    assert 'sdy.mesh @mesh = <["data"=8]>' in hlo, hlo[:500]
    assert '#sdy.sharding<@mesh, [{"data"}, {}]>' in hlo, hlo[:500]
    # Detach restores the single-device path.
    part.attach_mesh(None)
    assert part.mesh is None
    outs2 = part.run([x], batch_buckets=(8,))
    np.testing.assert_allclose(outs2[0], want[0], rtol=1e-5)


def test_attach_mesh_pads_to_data_axis_multiple():
    """No configured bucket divides the data axis: the pad falls back to
    the next multiple of ndata, never an indivisible bucket (static
    per-shard shapes)."""
    from min_tfs_client_tpu.parallel.mesh import make_mesh
    from min_tfs_client_tpu.servables.partition import _pad_interior

    padded, batch, bucket = _pad_interior(
        [np.ones((5, 3), np.float32)], (6, 7), ndata=4)
    assert (batch, bucket) == (5, 8)  # 6 and 7 skipped; 2*ndata
    assert padded[0].shape == (8, 3)

    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    part.attach_mesh(make_mesh({"data": 4}))
    x = np.ones((3, 3), np.float32)
    outs = part.run([x], batch_buckets=(6,))  # 6 % 4 != 0 -> bucket 4
    assert np.asarray(outs[0]).shape == (3, 4)


def test_attach_mesh_tp_lifts_large_interior_weights():
    """DPxTP mesh with the lift threshold lowered: the interior weight
    leaves the traced closure and becomes a 'model'-sharded jit
    argument; numerics stay exact."""
    from min_tfs_client_tpu.parallel.mesh import MODEL_AXIS, make_mesh

    gd = _classify_graph()
    part = try_partition(gd, ["x:0"], ["scores:0", "label:0"],
                         funclib=_FuncLib(None), tables=_tables())
    assert part is not None
    part.TP_MIN_BYTES = 1  # the 3x4 test weight qualifies
    mesh = make_mesh({"data": 4, "model": 2})
    part.attach_mesh(mesh)
    seg = part.segments[0]
    assert seg.param_refs == ["w:0"]  # lifted
    spec = seg.param_args[0].sharding.spec
    assert MODEL_AXIS in spec  # last divisible dim sharded over "model"
    x = np.random.default_rng(2).standard_normal((3, 3)).astype(np.float32)
    outs = part.run([x], batch_buckets=(4, 8))
    ref = GraphFunction(gd, ["x:0"], ["scores:0", "label:0"],
                        tables=_tables())
    want = ref([x], np)
    np.testing.assert_allclose(outs[0], want[0], rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(outs[1], object), want[1])
    # Detach restores the closed-over interior.
    part.attach_mesh(None)
    assert seg.param_refs == [] and seg.param_args == []
    assert seg.interior is seg.base_interior


def test_servable_attach_mesh_reaches_partition():
    """servable.attach_mesh no longer skips on_host signatures carrying
    a partition: the mesh lands on the interior AND on the signature
    (so round_up_batch agrees with the partition's divisible buckets).
    Pure-host signatures stay untouched."""
    import pathlib
    import tempfile

    from tests import fixtures
    from min_tfs_client_tpu.parallel.mesh import make_mesh
    from min_tfs_client_tpu.servables.graphdef_import import (
        load_saved_model,
    )
    from min_tfs_client_tpu.servables.servable import attach_mesh

    base = pathlib.Path(tempfile.mkdtemp()) / "imported"
    fixtures.write_imported_transformer_classify(
        base, seq=8, d_model=16, layers=1, vocab=32, labels=4)
    servable = load_saved_model(str(base / "1"), "imported", 1)
    sig = servable.signature("")
    assert sig.on_host and sig.partition is not None
    mesh = make_mesh({"data": 8})
    attach_mesh(servable, mesh, only_if_absent=True)
    assert sig.partition.mesh is mesh
    assert sig.mesh is mesh
    assert sig.round_up_batch(5) % 8 == 0
    # Idempotent + only_if_absent keeps the existing mesh.
    attach_mesh(servable, make_mesh({"data": 4}), only_if_absent=True)
    assert sig.partition.mesh is mesh
