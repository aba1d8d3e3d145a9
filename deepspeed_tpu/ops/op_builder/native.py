"""Native (C++) op building: g++ → shared object → ctypes.

TPU-native analog of the reference's JIT path in ``op_builder/builder.py``
(SURVEY.md §2.1): where the reference shells out to nvcc via torch
cpp_extension, we compile host-side C++ (csrc/) with g++ and bind via ctypes
(no pybind11 in this image).  ``DS_BUILD_*``-style forcing is honored through
``DS_TPU_REBUILD_OPS=1``.

The build uses ``-march=native``, so a library is only good on the kind of
CPU that built it, and the build directory travels with a copied checkout.
The output name therefore carries a hash of the source text, the flags and
the machine: a library built from other sources or on another CPU is never
found, and this process builds its own.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import platform
import subprocess
import threading
from typing import List

from deepspeed_tpu.utils.logging import logger

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
_BUILD_DIR = os.environ.get(
    "DS_TPU_BUILD_DIR", os.path.join(_REPO_ROOT, "build", "ops"))
_LOCK = threading.Lock()


@functools.lru_cache(maxsize=None)
def _machine_id() -> str:
    """What ``-march=native`` compiles for: the architecture plus the first
    CPU's model and feature flags."""
    parts = [platform.machine()]
    seen = set()
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                key = line.split(":")[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen.add(key)
                    parts.append(line.strip())
    except OSError:
        pass
    return "\n".join(parts)


class NativeOpBuilder:
    NAME: str = ""
    SOURCES: List[str] = []          # relative to repo root
    CXX_FLAGS = ["-O3", "-std=c++17", "-fPIC", "-shared", "-march=native",
                 "-funroll-loops"]
    LDFLAGS = ["-lpthread"]

    _cache: dict = {}

    def build_key(self) -> str:
        """Hash of everything the library's bytes depend on."""
        h = hashlib.sha256()
        for src in self.SOURCES:
            with open(os.path.join(_REPO_ROOT, src), "rb") as fh:
                h.update(fh.read())
        h.update("\0".join([*self.CXX_FLAGS, *self.LDFLAGS,
                            _machine_id()]).encode())
        return h.hexdigest()[:16]

    def lib_path(self) -> str:
        return os.path.join(_BUILD_DIR,
                            f"lib_ds_{self.NAME}_{self.build_key()}.so")

    def _needs_build(self) -> bool:
        return bool(os.environ.get("DS_TPU_REBUILD_OPS")) \
            or not os.path.exists(self.lib_path())

    def build(self) -> str:
        with _LOCK:
            if not self._needs_build():
                return self.lib_path()
            os.makedirs(_BUILD_DIR, exist_ok=True)
            srcs = [os.path.join(_REPO_ROOT, s) for s in self.SOURCES]
            out = self.lib_path()
            cmd = ["g++", *self.CXX_FLAGS, *srcs, "-o", out + ".tmp", *self.LDFLAGS]
            logger.info("building native op %s: %s", self.NAME, " ".join(cmd))
            try:
                subprocess.run(cmd, check=True, capture_output=True, text=True)
            except subprocess.CalledProcessError as e:  # pragma: no cover
                raise RuntimeError(
                    f"native build of {self.NAME} failed:\n{e.stderr}") from e
            os.replace(out + ".tmp", out)
            return out

    def is_compatible(self) -> bool:
        try:
            self.load()
            return True
        except Exception as e:
            logger.warning("native op %s unavailable: %s", self.NAME, e)
            return False

    def load(self) -> ctypes.CDLL:
        key = self.NAME
        if key not in NativeOpBuilder._cache:
            NativeOpBuilder._cache[key] = ctypes.CDLL(self.build())
        return NativeOpBuilder._cache[key]


class CPUAdamBuilder(NativeOpBuilder):
    NAME = "cpu_adam"
    SOURCES = ["csrc/cpu_adam/cpu_adam.cpp"]

    def load(self) -> ctypes.CDLL:
        lib = super().load()
        i64, f, i, p = ctypes.c_int64, ctypes.c_float, ctypes.c_int, ctypes.c_void_p
        lib.ds_adam_step.argtypes = [i64, p, p, p, p, i64, f, f, f, f, f, i]
        lib.ds_adam_step.restype = None
        lib.ds_adam_step_bf16g.argtypes = [i64, p, p, p, p, p, i64, f, f, f, f, f, i]
        lib.ds_adam_step_bf16g.restype = None
        lib.ds_adagrad_step.argtypes = [i64, p, p, p, f, f, f]
        lib.ds_adagrad_step.restype = None
        lib.ds_lion_step.argtypes = [i64, p, p, p, f, f, f, f]
        lib.ds_lion_step.restype = None
        return lib


def available_ops():
    """(name, compatible, note) rows for every native builder — the data
    behind ``ds_report`` (reference: op compatibility matrix in
    env_report.py)."""
    rows = []
    for cls in (CPUAdamBuilder, AsyncIOBuilder):
        b = cls()
        built = os.path.exists(b.lib_path())
        try:
            ok = b.is_compatible()
            note = ("prebuilt" if built else "jit-built") if ok else "build failed"
        except Exception as exc:  # pragma: no cover
            ok, note = False, str(exc)
        rows.append((f"native.{cls.NAME}", ok, note))
    return rows


class AsyncIOBuilder(NativeOpBuilder):
    NAME = "aio"
    SOURCES = ["csrc/aio/ds_aio.cpp"]

    def load(self) -> ctypes.CDLL:
        lib = super().load()
        i64, i, p, cp = ctypes.c_int64, ctypes.c_int, ctypes.c_void_p, ctypes.c_char_p
        lib.ds_aio_handle_new.argtypes = [i, i, i, i, i, i]
        lib.ds_aio_handle_new.restype = p
        lib.ds_aio_handle_free.argtypes = [p]
        lib.ds_aio_pread_async.argtypes = [p, cp, p, i64, i64]
        lib.ds_aio_pwrite_async.argtypes = [p, cp, p, i64, i64]
        lib.ds_aio_wait.argtypes = [p]
        lib.ds_aio_wait.restype = i64
        lib.ds_aio_read.argtypes = [p, cp, p, i64, i64]
        lib.ds_aio_read.restype = i64
        lib.ds_aio_write.argtypes = [p, cp, p, i64, i64]
        lib.ds_aio_write.restype = i64
        return lib
