"""Native op builder: JIT-compiles ``csrc/*.cpp`` into shared libraries.

Parity target: ``op_builder/builder.py`` — ``OpBuilder.jit_load()`` (:545) compiles
CUDA/C++ with ninja at first use and caches the module. Here the toolchain is plain
g++ (→ .so loaded via ctypes; pybind11 is not in this image), the cache key is a hash
of the sources, the flags and the host CPU's feature flags (``-march=native`` output
must not travel to another machine), and ops are host-side C++ (device code is Pallas,
which XLA JITs). Nothing here runs at ``import deepspeed_tpu``: a library is built and
loaded at the first use of the op that needs it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sysconfig
from typing import List, Optional

from deepspeed_tpu.utils.logging import log_dist, logger

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
_DEFAULT_BUILD_DIR = os.environ.get(
    "DSTPU_BUILD_DIR", os.path.join(_REPO_ROOT, ".dstpu_build"))


def _host_cpu_flags() -> bytes:
    try:
        with open("/proc/cpuinfo", "rb") as f:
            return next((ln for ln in f if ln.startswith(b"flags")), b"")
    except OSError:
        return b""


class NativeOpBuilder:
    """g++ → .so → ctypes loader, cached by content hash (jit_load parity)."""

    NAME = "native"
    SOURCES: List[str] = []
    EXTRA_FLAGS: List[str] = []

    def __init__(self, build_dir: Optional[str] = None):
        self.build_dir = build_dir or _DEFAULT_BUILD_DIR
        self._lib: Optional[ctypes.CDLL] = None

    def absolute_sources(self) -> List[str]:
        return [os.path.join(_REPO_ROOT, s) for s in self.SOURCES]

    def _command(self) -> List[str]:
        return (["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-march=native",
                 "-fopenmp"] + self.EXTRA_FLAGS + self.absolute_sources()
                + ["-lpthread"])

    def so_path(self) -> str:
        h = hashlib.sha256(" ".join(self._command()).encode())
        for s in self.absolute_sources():
            with open(s, "rb") as f:
                h.update(f.read())
        h.update(_host_cpu_flags())
        return os.path.join(self.build_dir,
                            f"lib{self.NAME}-{h.hexdigest()[:16]}.so")

    def is_compatible(self, verbose: bool = False) -> bool:
        from shutil import which

        ok = which("g++") is not None and all(
            os.path.exists(s) for s in self.absolute_sources())
        if not ok and verbose:
            logger.warning(f"{self.NAME}: g++ or sources missing")
        return ok

    def build(self) -> str:
        os.makedirs(self.build_dir, exist_ok=True)
        so = self.so_path()
        if os.path.exists(so):
            return so
        tmp = f"{so}.{os.getpid()}.tmp"     # concurrent builders never share
        cmd = self._command() + ["-o", tmp]
        log_dist(f"building native op {self.NAME}: {' '.join(cmd)}")
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as e:
            # -march=native / openmp can be unavailable in exotic toolchains
            fallback = [a for a in cmd if a not in ("-march=native", "-fopenmp")]
            logger.warning(f"native build retry without arch/openmp: {e.stderr[:300]}")
            subprocess.run(fallback, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
        return so

    def load(self) -> ctypes.CDLL:
        if self._lib is None:
            self._lib = ctypes.CDLL(self.build())
        return self._lib


class CPUAdamBuilder(NativeOpBuilder):
    """reference op_builder/cpu_adam.py parity."""

    NAME = "dstpu_cpu_adam"
    SOURCES = ["csrc/cpu_adam.cpp"]


class AsyncIOBuilder(NativeOpBuilder):
    """reference op_builder/async_io.py parity."""

    NAME = "dstpu_aio"
    SOURCES = ["csrc/aio.cpp"]
