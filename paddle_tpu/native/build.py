"""Build the native runtime library (g++ → libpaddle_tpu_native.so).

The reference ships its runtime as compiled C++/Go (recordio chunking +
the Go master, reference: go/master/service.go); ours compiles on first
use and caches the .so beside the sources. Builds are multi-process safe:
the compiler writes a temp file that is os.replace()d into place under an
fcntl file lock, so concurrent trainers never dlopen a half-written .so.

Staleness is keyed on a hash of the sources and the compiler command,
kept in `<lib>.srchash` beside the library, not on mtimes: the tree
gets copied (ignored `lib*.so` files included) by tools that flatten or
reorder mtimes, and a library that no longer matches its sources must
rebuild wherever it lands.
"""

from __future__ import annotations

import contextlib
import fcntl
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_SOURCES = ["recordio.cc", "taskqueue.cc", "loader.cc"]
_LIB = os.path.join(_DIR, "libpaddle_tpu_native.so")
_lock = threading.Lock()
#: library file name -> "built" | "reused", for every library this
#: process has asked for (chip_smoke.py prints it in its header)
_ensured: dict = {}


def lib_path() -> str:
    return _LIB


@contextlib.contextmanager
def _file_lock(path: str):
    """Advisory cross-process lock (multi-process trainers may race the
    first build; an in-process threading.Lock alone is not enough)."""
    with open(path, "w") as fh:
        fcntl.flock(fh, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(fh, fcntl.LOCK_UN)


def ensured() -> dict:
    """Which native libraries this process has loaded so far, and
    whether each was compiled here or found fresh."""
    return dict(_ensured)


def _source_hash(flags: list, srcs: list) -> str:
    h = hashlib.sha256("\0".join(flags).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(b"\0" + os.path.basename(src).encode() + b"\0")
            h.update(f.read())
    return h.hexdigest()


def _ensure(lib: str, cmd_prefix: list, srcs: list, force: bool,
            link_flags: tuple = ()) -> str:
    """Compile `lib` as `cmd_prefix srcs link_flags -o lib` unless a
    library built from exactly these bytes and this command is already
    in place."""
    stamp = lib + ".srchash"
    with _lock, _file_lock(lib + ".lock"):
        want = _source_hash([*cmd_prefix, *link_flags], srcs)
        fresh = False
        if not force and os.path.exists(lib) and os.path.exists(stamp):
            with open(stamp) as f:
                fresh = f.read().strip() == want
        if not fresh:
            tmp = f"{lib}.tmp.{os.getpid()}"
            try:
                subprocess.run([*cmd_prefix, *srcs, *link_flags, "-o", tmp],
                               check=True, capture_output=True, text=True)
                os.replace(tmp, lib)  # atomic publish
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            with open(stamp, "w") as f:
                f.write(want + "\n")
        _ensured[os.path.basename(lib)] = "reused" if fresh else "built"
        return lib


def ensure_built(force: bool = False) -> str:
    """Compile the shared library if missing or stale; returns its path."""
    return _ensure(_LIB, ["g++", "-O2", "-std=c++17", "-fPIC", "-shared",
                          "-pthread", "-Wall"],
                   [os.path.join(_SRC, s) for s in _SOURCES], force)


_CAPI_SRC = os.path.join(_SRC, "capi.cc")
_CAPI_LIB = os.path.join(_DIR, "libpaddle_tpu_capi.so")


def _python_config(flag: str) -> list:
    import sysconfig

    args = [flag] + (["--embed"] if flag == "--ldflags" else [])
    exe = f"python{sysconfig.get_python_version()}-config"
    try:
        out = subprocess.run([exe, *args], check=True,
                             capture_output=True, text=True).stdout
    except (subprocess.CalledProcessError, FileNotFoundError):
        out = subprocess.run(["python3-config", *args], check=True,
                             capture_output=True, text=True).stdout
    return out.split()


def ensure_capi_built(force: bool = False) -> str:
    """Compile the C inference ABI library (embeds CPython)."""
    return _ensure(_CAPI_LIB,
                   ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-Wall",
                    *_python_config("--includes")],
                   [_CAPI_SRC], force,
                   link_flags=tuple(_python_config("--ldflags")))


_INFER_SRC = os.path.join(_SRC, "infer.cc")
_INFER_LIB = os.path.join(_DIR, "libpaddle_tpu_infer.so")


def ensure_infer_built(force: bool = False) -> str:
    """Compile the Python-FREE native inference engine (infer.cc).

    Unlike ensure_capi_built, this links against nothing but
    libc/libm/OpenMP — the artifact consumer needs no interpreter
    (the reference capi's serving contract, capi/gradient_machine.h:36).
    """
    return _ensure(_INFER_LIB,
                   ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-Wall",
                    "-fopenmp"], [_INFER_SRC], force)


_PJRT_SRC = os.path.join(_SRC, "pjrt_serve.cc")
_PJRT_LIB = os.path.join(_DIR, "libpaddle_tpu_pjrt.so")


def _pjrt_include_dir():
    """xla/pjrt/c/pjrt_c_api.h ships in the tensorflow wheel's include
    tree (no other copy exists in this image). Located WITHOUT importing
    tensorflow — the module spec is enough."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise RuntimeError(
            "pjrt_c_api.h not found: the tensorflow package (which "
            "vendors the XLA PJRT headers) is not installed")
    return os.path.join(spec.submodule_search_locations[0], "include")


def ensure_pjrt_built(force: bool = False) -> str:
    """Compile the PJRT-C serving library (Python-free TPU inference:
    dlopens the platform plugin, e.g. libtpu.so, at runtime)."""
    return _ensure(_PJRT_LIB,
                   ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-Wall",
                    f"-I{_pjrt_include_dir()}"], [_PJRT_SRC], force,
                   link_flags=("-ldl",))
