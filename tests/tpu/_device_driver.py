"""On-hardware checks, executed in a fresh process with the REAL backend.

Run by tests/tpu/test_on_device.py in a subprocess (the pytest process
itself is pinned to a CPU mesh by tests/conftest.py, and jax cannot switch
backends mid-process). Each check prints one JSON line
{"check": name, "ok": bool, ...}; the wrapper asserts on them, and the
exit code is non-zero when any check failed.
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


_LAST_EMIT = time.monotonic()
_FAILED: list[str] = []


def emit(check: str, ok: bool, **extra) -> None:
    global _LAST_EMIT
    now = time.monotonic()
    extra.setdefault("ms", round((now - _LAST_EMIT) * 1e3, 1))
    _LAST_EMIT = now
    if not ok:
        _FAILED.append(check)
    print(json.dumps({"check": check, "ok": ok, **extra}), flush=True)


def main() -> int:
    from min_tfs_client_tpu.utils import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    emit("backend", dev.platform == "tpu", platform=str(dev.platform),
         kind=dev.device_kind)
    if dev.platform != "tpu":
        return 1  # nothing below means anything on another backend

    # -- 1. flash attention on the MXU vs the jnp oracle -------------------
    from min_tfs_client_tpu.ops.attention import (
        attention_reference,
        flash_attention,
    )

    rng = np.random.default_rng(0)
    b, h, s, d = 2, 4, 256, 64
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    lengths = jnp.asarray([s, s // 3], jnp.int32)

    # The serving path goes through the `attention` DISPATCHER — assert it
    # actually picks the Pallas kernel on this hardware (round-3 verdict:
    # "confirm the served BERT path hits the flash kernel, not
    # attention_reference"). The pallas lowering appears as a custom call.
    from min_tfs_client_tpu.ops.attention import attention

    lowered = jax.jit(
        lambda q, k, v: attention(q, k, v, lengths=lengths)).lower(q, k, v)
    text = lowered.as_text()
    dispatched = "tpu_custom_call" in text or "custom_call" in text
    emit("flash_dispatch", dispatched,
         note="attention() lowers to a pallas custom call on this backend")
    for name, kwargs in [("plain", {}), ("causal", {"causal": True}),
                         ("lengths", {"lengths": lengths})]:
        t0 = time.perf_counter()
        got = np.asarray(flash_attention(q, k, v, **kwargs),
                         np.float32)
        dt = (time.perf_counter() - t0) * 1e3
        want = np.asarray(attention_reference(q, k, v, **kwargs), np.float32)
        # bf16 inputs: compare against the oracle at bf16 resolution.
        err = float(np.max(np.abs(got - want)))
        emit(f"flash_attention/{name}", err < 0.06, max_err=err,
             ms=round(dt, 2))

    # -- 1b. ragged paged attention, with bias, past one page ---------------
    # T5's only path (models/t5.py always passes bias=), at a page size
    # below 128 and a table wider than one page: the shape Mosaic refused
    # until the bias tile spanned its array's last two dims. Sq=1 is a
    # decode tick, Sq=5 a verify block / prefill chunk.
    from min_tfs_client_tpu.ops.attention import (
        PagedKV,
        paged_attention,
        paged_attention_reference,
    )

    pb, ph, pd, page, width = 4, 8, 64, 16, 3
    n_pages = pb * width
    shape = PagedKV.arena_shape(n_pages, page, (ph, pd))
    k_pages = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v_pages = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(n_pages).reshape(pb, width), jnp.int32)
    for sq in (1, 5):
        pq = jnp.asarray(rng.standard_normal((pb, ph, sq, pd)), jnp.bfloat16)
        bias = jnp.asarray(
            rng.standard_normal((pb, ph, sq, width * page)), jnp.float32)
        plens = jnp.asarray([width * page, page + 3, page, sq], jnp.int32)
        lowered = jax.jit(paged_attention).lower(
            pq, k_pages, v_pages, tables, plens, bias=bias).as_text()
        got = np.asarray(paged_attention(
            pq, k_pages, v_pages, tables, plens, bias=bias), np.float32)
        want = np.asarray(paged_attention_reference(
            pq, k_pages, v_pages, tables, plens, bias=bias), np.float32)
        err = float(np.max(np.abs(got - want)))
        emit(f"paged_bias_multipage/sq{sq}",
             "tpu_custom_call" in lowered and err < 0.06, max_err=err,
             dispatched="tpu_custom_call" in lowered)

    # -- 1c. a decode step's latent attention: the kernel against the jnp
    # form at both cells' shapes (Xing's caches of 2,176 positions and
    # YaRN's scale, Ling's of 2,304 and the plain one): ragged positions,
    # a row nobody owns, the row written where it lies and no other moved.
    from min_tfs_client_tpu.models import latent

    heads, nope, rope_lanes, dv, rank = 32, 128, 64, 128, 512
    for cell, (positions, scale) in {
            "xing": (2176, 192 ** -0.5 * latent.yarn_mscale(64.0, 1.0) ** 2),
            "ling": (2304, 192 ** -0.5)}.items():
        lb = 8
        kvb = jnp.asarray(rng.standard_normal((rank, heads * (nope + dv)))
                          * rank ** -0.5, jnp.bfloat16)
        lq = jnp.asarray(rng.standard_normal((lb, heads, nope + rope_lanes)),
                         jnp.bfloat16)
        cache = jnp.asarray(rng.standard_normal((lb, 1, positions, 640)),
                            jnp.bfloat16).at[..., 576:].set(0)
        row = jnp.asarray(rng.standard_normal((lb, 640)),
                          jnp.bfloat16).at[..., 576:].set(0)
        at = jnp.asarray([0, 15, 16, 127, 128, 1030, positions - 1, 700],
                         jnp.int32)
        owned = jnp.asarray([True] * 7 + [False])
        sizes = dict(nope=nope, v_head_dim=dv, scale=scale)
        step = jax.jit(lambda *a: latent.absorbed_attention(*a, **sizes))
        lowered = step.lower(kvb, lq, cache, row, at, owned).as_text()
        got, after, copied = step(kvb, lq, cache, row, at, owned)
        shut = latent._on_tpu
        latent._on_tpu = lambda: False      # the jnp form, on the chip
        try:
            want, want_after, held = jax.jit(
                lambda *a: latent.absorbed_attention(*a, **sizes))(
                    kvb, lq, cache, row, at, owned)
        finally:
            latent._on_tpu = shut
        err = float(jnp.max(jnp.abs(got[:7] - want[:7])))
        emit(f"latent_step/{cell}",
             "_latent_step_kernel" in lowered and err < 0.05
             and not bool(jnp.any(got[7]))
             and bool(jnp.all(after[:7] == want_after[:7]))
             and bool(jnp.all(after[7] == cache[7]))
             and copied.tolist() == [128, 128, 128, 128, 256, 1152,
                                     positions, 0]
             and held.tolist() == [positions] * lb,
             max_err=err, scale_of_values=float(jnp.std(want[:7])))

    # -- 2. bucketed Predict through the serving stack on device -----------
    import pathlib
    import tempfile

    from tests import fixtures
    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_tier_")) / "matmul"
    fixtures.write_matmul_model(base)
    client = TensorServingClient(f"tpu://{base}")
    x = rng.standard_normal((3, 8)).astype(np.float32)  # 3 -> bucket 4
    resp = client.predict_request("matmul", {"x": x})
    probs = tensor_proto_to_ndarray(resp.outputs["probs"])
    ok = (probs.shape == (3, 4)
          and np.allclose(probs.sum(-1), 1.0, atol=1e-3))
    emit("bucketed_predict", bool(ok), shape=list(probs.shape))

    # -- 3. mesh attach smoke (1-device data mesh on the chip) -------------
    from min_tfs_client_tpu.parallel.mesh import make_mesh
    from min_tfs_client_tpu.client.inprocess import _registry

    server = _registry[str(base)]
    from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
    from min_tfs_client_tpu.servables.servable import attach_mesh

    spec = apis.ModelSpec()
    spec.name = "matmul"
    with server.core.servable_handle(spec) as handle:
        attach_mesh(handle.servable, make_mesh({"data": 1}))
    resp2 = client.predict_request("matmul", {"x": x})
    probs2 = tensor_proto_to_ndarray(resp2.outputs["probs"])
    emit("mesh_attach_predict",
         bool(np.allclose(probs, probs2, atol=1e-5)))

    # -- 4. int8 quantized serving on device vs full precision -------------
    # Each trailing check fails in isolation (emit ok=False, and a
    # non-zero exit at the end) so one failure does not hide the rest.
    try:
        import dataclasses

        from min_tfs_client_tpu.models import bert, export

        config = bert.BertConfig.tiny(num_labels=4)
        params = bert.init_params(jax.random.PRNGKey(0), config)
        qbase = (pathlib.Path(tempfile.mkdtemp(prefix="tpu_tier_"))
                 / "bert_q8")
        export.export_servable(qbase, 1, "bert", dataclasses.asdict(config),
                               params, signature_kwargs={"seq_len": 16},
                               quantize="int8")
        qclient = TensorServingClient(f"tpu://{qbase}")
        ids = rng.integers(0, config.vocab_size, (4, 16)).astype(np.int32)
        mask = np.ones((4, 16), np.int32)
        resp = qclient.predict_request(
            "bert_q8", {"input_ids": ids, "attention_mask": mask})
        q_logits = tensor_proto_to_ndarray(resp.outputs["logits"])
        fp_logits = np.asarray(bert.logits_fn(params, config, ids, mask),
                               np.float32)
        rel = float(np.max(np.abs(q_logits - fp_logits))
                    / max(float(np.max(np.abs(fp_logits))), 1e-6))
        emit("int8_predict",
             bool(np.isfinite(q_logits).all() and rel < 0.35),
             rel_dev=round(rel, 4))
    except Exception as exc:  # noqa: BLE001 - per-check isolation
        emit("int8_predict", False, error=repr(exc)[:500])

    # -- 4b. partitioned imported SavedModel: interior on the chip ---------
    try:
        from min_tfs_client_tpu.servables.graphdef_import import (
            load_saved_model,
        )

        ibase = (pathlib.Path(tempfile.mkdtemp(prefix="tpu_tier_"))
                 / "imported")
        fixtures.write_imported_transformer_classify(ibase, seq=32,
                                                     d_model=64, layers=1)
        probe = load_saved_model(str(ibase / "1"), "imported", 1)
        part = probe.signature("").partition
        iclient = TensorServingClient(f"tpu://{ibase}")
        feats = [{"ids": rng.integers(0, 2048, 32)} for _ in range(3)]
        iresp = iclient.classification_request("imported", feats,
                                               timeout=300)
        labels_ok = all(
            cl.classes[0].label.startswith("class_")
            for cl in iresp.result.classifications)
        emit("partitioned_import_classify",
             bool(part is not None and labels_ok
                  and len(iresp.result.classifications) == 3),
             partitioned=part is not None,
             interior_ops=(part.stats["interior_ops"][:6]
                           if part else []))
    except Exception as exc:  # noqa: BLE001 - per-check isolation
        emit("partitioned_import_classify", False, error=repr(exc)[:500])

    # -- 5. continuous-batching decode sessions on device ------------------
    try:
        from min_tfs_client_tpu.models import t5

        t5c = t5.T5Config.tiny()
        t5p = t5.init_params(jax.random.PRNGKey(0), t5c)
        sigs = t5.build_session_signatures(
            t5p, t5c, seq_len=12, max_decode_len=6, max_sessions=4,
            continuous_batching=True)
        prompt = rng.integers(2, t5c.vocab_size, (1, 12)).astype(np.int32)
        lengths = np.sum(prompt != t5c.pad_id, axis=-1).astype(np.int32)
        want = np.asarray(t5.greedy_decode(
            t5p, t5c, prompt, lengths, max_decode_len=6)[0])[0]
        sid = np.asarray(b"tier", object)
        sigs["decode_init"].run({"session_id": sid, "input_ids": prompt})
        toks = [int(sigs["decode_step"].run(
            {"session_id": sid})["token"][0]) for _ in range(6)]
        emit("continuous_batching_decode", toks == list(want), tokens=toks)
    except Exception as exc:  # noqa: BLE001 - per-check isolation
        emit("continuous_batching_decode", False, error=repr(exc)[:500])
    if _FAILED:
        print(f"failed checks: {_FAILED}", file=sys.stderr)
    return 1 if _FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
