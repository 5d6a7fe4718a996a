"""Export model families as servable version directories.

The reference's unit of deployment is a numbered SavedModel version dir
(file_system_storage_path_source watching base_path/<N>/, SURVEY.md §2.4);
this framework keeps that layout for its native families:

    <base_path>/<version>/
        servable.py    loader stub (the "jax" platform contract)
        config.json    {"family", "config", "signature_kwargs"}
        params.npz     flattened param pytree

so native models hot-load/reload/version exactly like imported GraphDefs.
"""

from __future__ import annotations

import importlib
import json
import pathlib
from typing import Mapping

import jax
import numpy as np

# A family whose module (models/<family>.py) needs no more of the loader
# than its config class and which JSON lists are tuples to it: {family:
# (config class, config keywords, signature keywords)}, a line a family.
_PLAIN_FAMILIES = {
    "resnet": ("ResNetConfig", ("stage_sizes",), ()),
    "use": ("USEConfig", ("seq_buckets",), ("batch_buckets",)),
    "mimo": ("MimoConfig", (), ("batch_buckets",)),
    "granite_hybrid": ("GraniteHybridConfig", (), ("batch_buckets",)),
    "ling_hybrid": ("LingHybridConfig", (), ("batch_buckets",)),
    "xing": ("XingConfig", (), ("batch_buckets",)),
}
FAMILIES = ("bert", "t5", *_PLAIN_FAMILIES)

_SERVABLE_STUB = """\
# Auto-generated by min_tfs_client_tpu.models.export — loads a native
# model-family servable from this directory's config.json + params.npz.
def build(path):
    from min_tfs_client_tpu.models.export import load_signatures

    return load_signatures(path)
"""


# -- param pytree <-> npz ----------------------------------------------------


def _key_str(path) -> str:
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        else:  # pragma: no cover
            parts.append(str(entry))
    return ".".join(parts)


def flatten_params(params) -> dict[str, np.ndarray]:
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    return {_key_str(path): np.asarray(leaf) for path, leaf in leaves}


def unflatten_params(flat: Mapping[str, np.ndarray]):
    root: dict = {}
    for key, value in flat.items():
        parts = key.split(".")
        node = root
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return _listify(root)


def _listify(node):
    """Dicts whose keys are exactly 0..n-1 were lists before flattening."""
    if not isinstance(node, dict):
        return node
    out = {k: _listify(v) for k, v in node.items()}
    if out and all(k.isdigit() for k in out):
        indices = sorted(int(k) for k in out)
        if indices == list(range(len(indices))):
            return [out[str(i)] for i in indices]
    return out


def save_params(path, params) -> None:
    np.savez(path, **flatten_params(params))


def load_params(path):
    import jax.numpy as jnp

    import ml_dtypes

    def restore(arr):
        # npz stores bfloat16 as raw void16 pairs; view the dtype back.
        if arr.dtype == np.dtype("V2"):
            arr = arr.view(ml_dtypes.bfloat16)
        return jnp.asarray(arr)

    with np.load(path, allow_pickle=False) as data:
        # jnp now, not at trace time: np leaves break traced indexing
        # (embedding[ids]) and would re-upload to HBM on every call.
        return unflatten_params({k: restore(data[k]) for k in data.files})


# -- export / load -----------------------------------------------------------


def export_servable(base_path, version: int, family: str, config_kwargs: dict,
                    params, signature_kwargs: dict | None = None,
                    sharding: dict | None = None,
                    draft: tuple[dict, object] | None = None,
                    quantize: str | None = None,
                    quantize_guard: float | None = None,
                    pipeline: dict | None = None) -> pathlib.Path:
    """`sharding` (optional): {"axes": {"data": -1, "model": 2}} — at load
    time the param pytree is tensor-parallel sharded over that mesh
    (parallel/sharding.py Megatron specs); an "expert" axis shards MoE
    weights expert-parallel instead. Absent or infeasible (too few
    devices) the servable loads replicated. `pipeline` (optional, bert):
    {"stages": N, "n_micro": M} — serve the layer stack as a GPipe
    microbatch pipeline over an N-device stage mesh. The reference has no
    in-server model parallelism at all (SURVEY.md §2.11)."""
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; known: {FAMILIES}")
    if pipeline is not None:
        # Fail at export, where the config is in hand — not at server
        # load, where a bad version dir silently never becomes available.
        if family not in ("bert", "t5"):
            raise ValueError("pipeline serving is implemented for the bert "
                             "(uniform stages) and t5 (encoder stack) "
                             f"families, not {family!r}")
        if sharding:
            raise ValueError(
                "pipeline and sharding configs cannot combine: the stage "
                "mesh owns the param layout (TP-within-stage not "
                "implemented)")
        if (signature_kwargs or {}).get("long_context_seq"):
            raise ValueError(
                "pipeline and long_context_seq cannot combine: the "
                "ring-attention path needs the standard param layout")
        stages = int(pipeline["stages"])
        if family == "bert":
            from min_tfs_client_tpu.models import bert as _bert

            cfg = _bert.BertConfig(**config_kwargs)
            if cfg.num_layers % stages:
                raise ValueError(
                    f"num_layers {cfg.num_layers} not divisible by "
                    f"{stages} pipeline stages")
            if cfg.moe_experts:
                raise ValueError(
                    "pipeline and moe_experts cannot combine: "
                    "per-microbatch expert capacity diverges from the "
                    "sequential routing semantics")
        else:
            from min_tfs_client_tpu.models import t5 as _t5

            t5_cfg = _t5.T5Config(**config_kwargs)
            if t5_cfg.num_encoder_layers % stages:
                raise ValueError(
                    f"num_encoder_layers {t5_cfg.num_encoder_layers} not "
                    f"divisible by {stages} pipeline stages")
    sig_kwargs = dict(signature_kwargs or {})
    if "class_labels" in sig_kwargs and sig_kwargs["class_labels"]:
        sig_kwargs["class_labels"] = [
            label.decode("utf-8") if isinstance(label, bytes) else str(label)
            for label in sig_kwargs["class_labels"]]
    meta = {
        "family": family,
        "config": config_kwargs,
        "signature_kwargs": sig_kwargs,
    }
    if sharding:
        meta["sharding"] = sharding
    if pipeline is not None:
        meta["pipeline"] = {k: int(v) for k, v in pipeline.items()}
    if quantize is not None:
        # Validate (and guard) BEFORE any filesystem writes: a tripped
        # guard must not leave a partial version dir for a base-path
        # watcher to trip over.
        if quantize != "int8":
            raise ValueError(f"unknown quantize mode {quantize!r} "
                             "(supported: 'int8')")
        from min_tfs_client_tpu.models.quantize import quantize_tree

        q_params = quantize_tree(params)
        if quantize_guard is not None:
            _check_quantized_outputs(family, config_kwargs, sig_kwargs,
                                     params, q_params, quantize_guard)
        params = q_params
        meta["quantize"] = quantize
    vdir = pathlib.Path(base_path) / str(version)
    vdir.mkdir(parents=True, exist_ok=True)
    if draft is not None:
        # (draft_config_kwargs, draft_params): a smaller model of the
        # same family enabling speculative decoding at load time.
        draft_config_kwargs, draft_params = draft
        meta["draft"] = draft_config_kwargs
        save_params(vdir / "draft_params.npz", draft_params)
    (vdir / "config.json").write_text(json.dumps(meta, indent=2))
    save_params(vdir / "params.npz", params)
    (vdir / "servable.py").write_text(_SERVABLE_STUB)
    return vdir


def _probe_inputs(signature, *, batch: int = 4, seq: int = 8):
    """Random valid inputs from a signature's specs: ints in {0,1} (legal
    token ids for any vocab and legal mask values alike), floats N(0,1),
    unknown interior dims -> `seq`."""
    rng = np.random.default_rng(0)
    inputs = {}
    for alias, spec in signature.inputs.items():
        dims = [batch] + [seq if d is None else int(d)
                          for d in spec.shape[1:]]
        np_dt = spec.dtype.numpy_dtype
        if np_dt.kind in "iu":
            inputs[alias] = rng.integers(0, 2, dims).astype(np_dt)
        elif np_dt.kind == "f":
            inputs[alias] = rng.standard_normal(dims).astype(np_dt)
        else:
            return None  # string/exotic inputs: no generic probe
    return inputs


def _check_quantized_outputs(family, config_kwargs, sig_kwargs,
                             fp_params, q_params, max_rel: float) -> None:
    """Export-time accuracy guard (quantize_guard=<rel>): serve one probe
    batch through the default signature in fp and int8 and require the
    outputs to agree within `max_rel` relative deviation. Catches wrong
    scale axes / saturated channels where they are cheapest to catch —
    before the version directory exists."""
    fp_sigs = build_family_signatures(
        family, config_kwargs, dict(sig_kwargs), fp_params)
    q_sigs = build_family_signatures(
        family, config_kwargs, dict(sig_kwargs), q_params)
    fp_sig = fp_sigs.get("serving_default") or next(iter(fp_sigs.values()))
    q_sig = q_sigs.get("serving_default") or next(iter(q_sigs.values()))
    inputs = _probe_inputs(fp_sig)
    if inputs is None:
        raise ValueError(
            "quantize_guard: the default signature has non-numeric "
            "inputs; pass quantize_guard=None and validate accuracy "
            "with a model-specific test instead")
    fp_out = fp_sig.run(dict(inputs))
    q_out = q_sig.run(dict(inputs))
    compared = 0
    for alias, fp_arr in fp_out.items():
        fp_arr = np.asarray(fp_arr)
        if fp_arr.dtype.kind != "f":
            continue  # token ids etc.: max-rel over ids is meaningless
        fp_arr = fp_arr.astype(np.float32)
        q_arr = np.asarray(q_out[alias], np.float32)
        scale = max(float(np.max(np.abs(fp_arr))), 1e-6)
        rel = float(np.max(np.abs(q_arr - fp_arr))) / scale
        compared += 1
        if rel > max_rel:
            raise ValueError(
                f"int8 quantization deviates {rel:.4f} (> {max_rel}) on "
                f"output {alias!r} of the probe batch; refusing to "
                "export. Inspect per-layer scales or raise the guard.")
    if not compared:
        raise ValueError(
            "quantize_guard: the default signature produces no "
            "continuous (float) outputs to compare — e.g. decode token "
            "ids; pass quantize_guard=None and validate accuracy with a "
            "model-specific test instead")


def _maybe_shard_params(params, sharding_cfg: dict):
    """Place the pytree TP/DP-sharded over a mesh built from the config;
    fall back to replicated single-device placement when the host lacks
    the devices (the same export serves a 1-chip box and a pod slice).
    Returns (params, mesh-or-None); the mesh is attached to the built
    signatures so formed batches land data-sharded on it."""
    from min_tfs_client_tpu.parallel import (
        infer_transformer_specs,
        make_mesh,
        shard_params,
    )

    axes = {k: int(v) for k, v in sharding_cfg.get("axes", {}).items()}
    if not axes:
        return params, None
    try:
        mesh = make_mesh(axes)
    except ValueError:
        return params, None  # not enough devices: serve replicated
    specs = infer_transformer_specs(params, mesh=mesh)
    return shard_params(params, specs, mesh), mesh


def _stage_mesh(meta: dict, mesh, sig_kwargs: dict):
    """An export with a `pipeline` block is served over a stage mesh
    where the host has the devices: the mesh goes into the signatures'
    keywords and is the one they are attached to."""
    pipe = meta.get("pipeline")
    if not pipe or mesh is not None:
        return mesh
    from min_tfs_client_tpu.parallel.mesh import make_mesh
    from min_tfs_client_tpu.parallel.pipeline import STAGE_AXIS

    try:
        stages = make_mesh({STAGE_AXIS: int(pipe["stages"])})
    except ValueError:
        return None  # too few devices: serve sequential
    sig_kwargs["pipeline_mesh"] = stages
    if pipe.get("n_micro"):
        sig_kwargs["pipeline_n_micro"] = int(pipe["n_micro"])
    return stages


def load_signatures(path) -> dict:
    vdir = pathlib.Path(path)
    meta = json.loads((vdir / "config.json").read_text())
    family = meta["family"]
    config_kwargs = meta.get("config", {})
    sig_kwargs = dict(meta.get("signature_kwargs", {}))
    params = load_params(vdir / "params.npz")
    mesh = None
    if meta.get("sharding"):
        params, mesh = _maybe_shard_params(params, meta["sharding"])
    return build_family_signatures(family, config_kwargs, sig_kwargs,
                                   params, mesh=mesh, meta=meta, vdir=vdir)


def build_family_signatures(family, config_kwargs, sig_kwargs, params, *,
                            mesh=None, meta=None, vdir=None) -> dict:
    """Family dispatch shared by load_signatures and the export-time
    quantization guard (which builds fp and int8 variants in-memory)."""
    from min_tfs_client_tpu.servables.servable import attach_mesh

    meta = meta or {}
    sig_kwargs = dict(sig_kwargs)
    config_kwargs = dict(config_kwargs)

    if family == "bert":
        from min_tfs_client_tpu.models import bert

        if sig_kwargs.get("class_labels"):
            sig_kwargs["class_labels"] = [
                label.encode() for label in sig_kwargs["class_labels"]]
        config = bert.BertConfig(**config_kwargs)
        mesh = _stage_mesh(meta, mesh, sig_kwargs)
        return attach_mesh(
            bert.build_signatures(params, config, **sig_kwargs), mesh)
    if family == "t5":
        from min_tfs_client_tpu.models import t5

        config = t5.T5Config(**config_kwargs)
        mesh = _stage_mesh(meta, mesh, sig_kwargs)
        # Speculative decoding: a draft model exported alongside
        # (draft_params.npz + "draft" config block) adds a
        # decode_speculative signature — same outputs as decode,
        # token-exact, fewer target passes.
        if (meta.get("draft") is not None and vdir is not None
                and (vdir / "draft_params.npz").exists()):
            sig_kwargs["draft_params"] = load_params(
                vdir / "draft_params.npz")
            sig_kwargs["draft_config"] = t5.T5Config(**meta["draft"])
        return attach_mesh(
            t5.build_signatures(params, config, **sig_kwargs), mesh)
    if family not in _PLAIN_FAMILIES:  # pragma: no cover
        raise ValueError(f"unknown family {family!r}")
    config_class, config_tuples, sig_tuples = _PLAIN_FAMILIES[family]
    module = importlib.import_module(f"min_tfs_client_tpu.models.{family}")
    for kwargs, names in ((config_kwargs, config_tuples),
                          (sig_kwargs, sig_tuples)):
        kwargs.update((name, tuple(kwargs[name]))
                      for name in names if name in kwargs)
    config = getattr(module, config_class)(**config_kwargs)
    return attach_mesh(
        module.build_signatures(params, config, **sig_kwargs), mesh)
