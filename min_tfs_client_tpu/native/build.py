"""Build the native libraries with the system compiler.

Invoked lazily by their ctypes loaders (cached per process), or manually:
    python -m min_tfs_client_tpu.native.build

A library's file name carries a hash of its source and flags
(`libtpuserve-<hash>.so`), so a prebuilt one is reused exactly when it was
built from the source beside it. Modification times say nothing after a
copy or a checkout, and the gitignored `.so` files travel with copies of
the tree.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import shutil
import subprocess

NATIVE_DIR = pathlib.Path(__file__).resolve().parent
SRC = NATIVE_DIR / "tpuserve.cpp"
HTTP_SRC = NATIVE_DIR / "net_http.cpp"
JSON_SRC = NATIVE_DIR / "json_tensor.cpp"
_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]


def _compile(src: pathlib.Path, stem: str, extra: list[str],
             force: bool) -> pathlib.Path | None:
    key = hashlib.sha256(
        src.read_bytes() + " ".join(_FLAGS + extra).encode()).hexdigest()[:16]
    out = NATIVE_DIR / f"{stem}-{key}.so"
    if out.exists() and not force:
        return out
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        return None
    # Compile to a process-unique temp path, then atomically rename:
    # concurrent builders (threads or processes) each produce a whole .so
    # and the last rename wins — never a torn file under a CDLL load.
    tmp = NATIVE_DIR / f"{stem}.tmp{os.getpid()}.so"
    cmd = [cxx, *_FLAGS, "-o", str(tmp), str(src)] + extra
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)
    except (subprocess.CalledProcessError, OSError):
        tmp.unlink(missing_ok=True)
        return None
    for stale in NATIVE_DIR.glob(f"{stem}*.so"):
        if stale != out and ".tmp" not in stale.name:
            stale.unlink(missing_ok=True)
    return out


def build(force: bool = False) -> pathlib.Path | None:
    return _compile(SRC, "libtpuserve", [], force)


def build_http(force: bool = False) -> pathlib.Path | None:
    return _compile(HTTP_SRC, "libtpunethttp", ["-lz", "-lpthread"], force)


def build_json(force: bool = False) -> pathlib.Path | None:
    return _compile(JSON_SRC, "libtpujson", [], force)


if __name__ == "__main__":
    print(f"built: {build(force=True)}")
    print(f"built: {build_http(force=True)}")
    print(f"built: {build_json(force=True)}")
