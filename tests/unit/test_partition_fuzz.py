"""Property test for the partitioner: random host/device op DAGs must
produce EXACTLY the all-host interpreter's results when served through
try_partition (any segment choice, any cut set, any padding). This is
the correctness amplifier for the round-5 feature — the hand-written
tests cover known shapes; this covers the shapes nobody wrote down."""

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
WIDTH = 4  # every float tensor in the fuzz graph is [B, WIDTH]


def _build_random_graph(rng: np.random.Generator):
    """A random layered DAG over [B, WIDTH] float tensors with host ops
    (int->int and int->string table lookups via ArgMax) sprinkled in.
    Returns (graph_def, tables, fetch_refs)."""
    gd = tf_graph_pb2.GraphDef()

    def const(name, arr):
        n = gd.node.add()
        n.name = name
        n.op = "Const"
        n.attr["value"].tensor.CopyFrom(ndarray_to_tensor_proto(arr))
        return name

    ph = gd.node.add()
    ph.name = "x"
    ph.op = "Placeholder"
    ph.attr["dtype"].type = DT_FLOAT
    const("axis1", np.asarray(1, np.int32))

    tables = {
        "int_tbl": LookupTable(list(range(WIDTH)),
                               [v * 10 + 1 for v in range(WIDTH)], False),
        "str_tbl": LookupTable(list(range(WIDTH)),
                               [f"lbl{v}".encode() for v in range(WIDTH)],
                               True),
    }
    for tname, vdt in (("int_tbl", DT_INT64), ("str_tbl", DT_STRING)):
        t = gd.node.add()
        t.name = tname
        t.op = "HashTableV2"
        t.attr["key_dtype"].type = DT_INT64
        t.attr["value_dtype"].type = vdt
    const("int_dflt", np.asarray(-1, np.int64))
    const("str_dflt", np.asarray(b"UNK", object))

    floats = ["x"]  # names of [B, WIDTH] float tensors
    n_layers = int(rng.integers(3, 9))
    # Layer plan: random middle, but FORCE a leading matmul and (usually)
    # a host_roundtrip -> matmul tail, so the corpus reliably contains
    # FLOP-bearing segments on BOTH sides of a host island — the
    # multi-segment executor's load-bearing shape (two-tower DAGs).
    kinds = ["matmul"] + [
        str(rng.choice(["matmul", "relu", "softmax", "addc", "mulc",
                        "add2", "host_roundtrip"]))
        for _ in range(n_layers)]
    n_chained = 0
    if rng.random() < 0.7:
        # The tail CHAINS (consumes the previous layer's output) so the
        # second tower really sits downstream of the island.
        kinds += ["host_roundtrip", "matmul"]
        n_chained = 2
    for i, kind in enumerate(kinds):
        src = (floats[-1] if i >= len(kinds) - n_chained
               else floats[int(rng.integers(0, len(floats)))])
        name = f"n{i}"
        if kind == "matmul":
            w = const(f"w{i}", (rng.standard_normal((WIDTH, WIDTH)) * 0.4
                                ).astype(np.float32))
            node = gd.node.add()
            node.name = name
            node.op = "MatMul"
            node.input.extend([src, w])
        elif kind == "relu":
            node = gd.node.add()
            node.name = name
            node.op = "Relu"
            node.input.append(src)
        elif kind == "softmax":
            node = gd.node.add()
            node.name = name
            node.op = "Softmax"
            node.input.append(src)
        elif kind == "addc":
            c = const(f"c{i}", (rng.standard_normal((WIDTH,)) * 0.5
                                ).astype(np.float32))
            node = gd.node.add()
            node.name = name
            node.op = "AddV2"
            node.input.extend([src, c])
        elif kind == "mulc":
            c = const(f"c{i}", np.float32(rng.uniform(0.5, 1.5)))
            node = gd.node.add()
            node.name = name
            node.op = "Mul"
            node.input.extend([src, c])
        elif kind == "add2":
            other = floats[int(rng.integers(0, len(floats)))]
            node = gd.node.add()
            node.name = name
            node.op = "AddV2"
            node.input.extend([src, other])
        else:  # host_roundtrip: D -> H (int lookup) -> D again
            am = gd.node.add()
            am.name = f"{name}_arg"
            am.op = "ArgMax"
            am.input.extend([src, "axis1"])
            fd = gd.node.add()
            fd.name = f"{name}_map"
            fd.op = "LookupTableFindV2"
            fd.input.extend(["int_tbl", f"{name}_arg", "int_dflt"])
            ct = gd.node.add()
            ct.name = f"{name}_f"
            ct.op = "Cast"
            ct.input.append(f"{name}_map")
            ct.attr["SrcT"].type = DT_INT64
            ct.attr["DstT"].type = DT_FLOAT
            ed = gd.node.add()
            ed.name = f"{name}_col"
            ed.op = "ExpandDims"
            ed.input.extend([f"{name}_f", "axis1"])
            node = gd.node.add()
            node.name = name
            node.op = "AddV2"  # broadcast [B,1] onto [B,WIDTH]
            node.input.extend([src, f"{name}_col"])
        floats.append(name)

    fetches = [f"{floats[-1]}:0"]
    if rng.random() < 0.7:  # a string label fetch through the str table
        am = gd.node.add()
        am.name = "final_arg"
        am.op = "ArgMax"
        am.input.extend([floats[-1], "axis1"])
        fd = gd.node.add()
        fd.name = "final_label"
        fd.op = "LookupTableFindV2"
        fd.input.extend(["str_tbl", "final_arg", "str_dflt"])
        fetches.append("final_label:0")
    if len(floats) > 2 and rng.random() < 0.5:  # mid-graph fetch too
        fetches.append(f"{floats[int(rng.integers(1, len(floats)))]}:0")
    return gd, tables, fetches


@pytest.mark.parametrize("seed", range(12))
def test_partitioned_matches_all_host_on_random_graphs(seed):
    rng = np.random.default_rng(seed)
    gd, tables, fetches = _build_random_graph(rng)
    host_fn = GraphFunction(gd, ["x:0"], fetches, tables=tables)
    part = try_partition(gd, ["x:0"], fetches,
                         funclib=_FuncLib(None), tables=tables)

    for batch in (1, 3, 5):
        x = rng.standard_normal((batch, WIDTH)).astype(np.float32)
        want = host_fn([x], np)
        if part is None:
            continue  # host-only graphs stay host; nothing to compare
        got = part.run([x], batch_buckets=(1, 4, 8))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if w.dtype.kind in "OSU":
                np.testing.assert_array_equal(g.astype(object), w)
            else:
                np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_fuzz_corpus_actually_covers_multi_segment():
    """Guard on the generator, not the engine: the host_roundtrip islands
    must produce graphs that partition into >= 2 jitted segments, or the
    parametrized oracle check above silently stops covering the
    multi-segment executor."""
    multi = 0
    for seed in range(12):
        rng = np.random.default_rng(seed)
        gd, tables, fetches = _build_random_graph(rng)
        part = try_partition(gd, ["x:0"], fetches,
                             funclib=_FuncLib(None), tables=tables)
        if part is not None and part.stats["n_segments"] >= 2:
            multi += 1
    assert multi >= 2, f"only {multi}/12 seeds exercised multi-segment"


@pytest.mark.parametrize("seed", range(12))
def test_pipelined_matches_serial_on_random_graphs(seed):
    """ISSUE 5 oracle variant: the microbatch software pipeline must
    serve what the serial partition path serves on every random DAG — for
    multi-segment graphs it actually pipelines, for single-segment or
    declined shapes it must fall through to serial untouched.

    Integer and string outputs are held to array_equal: a dropped,
    doubled or reordered row shows there. Float outputs are held to the
    tolerance the serial path itself is held to against the all-host
    interpreter above, NOT to bit-identity: a microbatch pads to a
    smaller bucket than the whole batch, so its interiors run a program
    compiled at another batch size, and XLA's CPU matmul rounds the same
    row differently at another size (read 1.9e-6; the pipelined rows are
    bit-identical to the same rows served serially chunk by chunk, so
    the pipeline itself adds nothing). Bit-identity across bucket sizes
    was the wrong oracle."""
    rng = np.random.default_rng(seed)
    gd, tables, fetches = _build_random_graph(rng)
    part = try_partition(gd, ["x:0"], fetches,
                         funclib=_FuncLib(None), tables=tables)
    if part is None:
        pytest.skip("host-only graph for this seed")
    for batch in (8, 16, 23):
        x = rng.standard_normal((batch, WIDTH)).astype(np.float32)
        part.pipeline_depth = 1
        want = part.run([x], batch_buckets=(1, 4, 8, 16, 32))
        for depth in (2, 4, 8):
            part.pipeline_depth = depth
            try:
                got = part.run([x], batch_buckets=(1, 4, 8, 16, 32))
            finally:
                part.pipeline_depth = 1
            assert len(got) == len(want)
            for g, w in zip(got, want):
                g, w = np.asarray(g), np.asarray(w)
                if w.dtype.kind in "OSU":
                    np.testing.assert_array_equal(g.astype(object),
                                                  w.astype(object))
                elif w.dtype.kind == "f":
                    np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
                else:
                    np.testing.assert_array_equal(g, w)


def test_pipelined_fuzz_corpus_actually_pipelines():
    """Coverage guard for the variant above: enough seeds must take the
    pipelined path for real (multi-segment, batch large enough, not
    declined), or the check above silently collapses into
    serial-vs-serial."""
    pipelined = 0
    for seed in range(12):
        rng = np.random.default_rng(seed)
        gd, tables, fetches = _build_random_graph(rng)
        part = try_partition(gd, ["x:0"], fetches,
                             funclib=_FuncLib(None), tables=tables)
        if part is None or part.stats["n_segments"] < 2:
            continue
        x = rng.standard_normal((16, WIDTH)).astype(np.float32)
        serial_calls = []
        inner = part._run_serial
        part._run_serial = (
            lambda f, b, _i=inner, _c=serial_calls: (_c.append(True),
                                                     _i(f, b))[1])
        part.pipeline_depth = 4
        try:
            part.run([x], batch_buckets=(1, 4, 8, 16, 32))
        finally:
            part.pipeline_depth = 1
            del part._run_serial
        if not serial_calls:
            pipelined += 1
    assert pipelined >= 2, (
        f"only {pipelined}/12 seeds actually ran the microbatch pipeline")


@pytest.mark.parametrize("seed", range(0, 12, 3))
def test_partitioned_matches_all_host_on_the_mesh(seed):
    """Same oracle property with the 8-device CPU mesh attached: DP
    sharding + divisible padding must never change a value, multi-
    segment DAGs included."""
    from min_tfs_client_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(seed)
    gd, tables, fetches = _build_random_graph(rng)
    host_fn = GraphFunction(gd, ["x:0"], fetches, tables=tables)
    part = try_partition(gd, ["x:0"], fetches,
                         funclib=_FuncLib(None), tables=tables)
    if part is None:
        pytest.skip("host-only graph for this seed")
    part.attach_mesh(make_mesh({"data": 8}))
    for batch in (1, 5):
        x = rng.standard_normal((batch, WIDTH)).astype(np.float32)
        want = host_fn([x], np)
        got = part.run([x], batch_buckets=(1, 4, 8))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if w.dtype.kind in "OSU":
                np.testing.assert_array_equal(g.astype(object), w)
            else:
                np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
