"""Native op build system (equivalent of reference ``op_builder/builder.py``
``OpBuilder.load()/.jit_load()``:108,523).

The reference JIT-compiles CUDA extensions through torch's cpp_extension;
here a builder compiles its C++ sources with the system toolchain into a
shared library cached under ``<repo>/.build/`` and binds it with ctypes (the
image ships no pybind11).  ``is_compatible()`` gates on toolchain presence so
import never hard-fails -- callers fall back to the jnp path, mirroring the
reference's installed-vs-JIT-vs-incompatible decision tree.
"""

import ctypes
import hashlib
import os
import shutil
import subprocess
import tempfile
import threading

from ..utils.logging import logger

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_BUILD_DIR = os.environ.get("DST_BUILD_DIR", os.path.join(_REPO_ROOT, ".build"))
_LOCK = threading.Lock()


class OpBuilder:
    """Compile-and-load for one native op (C ABI .so via ctypes)."""

    NAME = "base"
    _cache = {}

    def sources(self):
        """C++ source paths relative to the repo's ``csrc/``."""
        raise NotImplementedError

    def extra_compile_args(self):
        return []

    def absolute_sources(self):
        return [os.path.join(_REPO_ROOT, "csrc", s) for s in self.sources()]

    def compiler(self):
        return os.environ.get("CXX") or shutil.which("g++") or shutil.which("clang++")

    def is_compatible(self, verbose=False):
        if self.compiler() is None:
            if verbose:
                logger.warning(f"[{self.NAME}] no C++ compiler found")
            return False
        missing = [s for s in self.absolute_sources() if not os.path.isfile(s)]
        if missing:
            if verbose:
                logger.warning(f"[{self.NAME}] missing sources: {missing}")
            return False
        return True

    def _lib_path(self):
        h = hashlib.sha256()
        for src in self.absolute_sources():
            with open(src, "rb") as f:
                h.update(f.read())
        h.update(" ".join(self.extra_compile_args()).encode())
        return os.path.join(_BUILD_DIR, f"lib{self.NAME}_{h.hexdigest()[:12]}.so")

    def build(self, verbose=False):
        """Compile the sources into the cached .so; returns its path."""
        lib = self._lib_path()
        with _LOCK:
            if os.path.isfile(lib):
                return lib
            os.makedirs(_BUILD_DIR, exist_ok=True)
            # other processes may build the same library at the same time
            # (xdist workers on a fresh tree): each compiles to a name of
            # its own and renames it in, so none can take another's file
            fd, tmp = tempfile.mkstemp(
                prefix=os.path.basename(lib) + ".", suffix=".tmp",
                dir=_BUILD_DIR)
            os.close(fd)
            cmd = [self.compiler(), "-O3", "-march=native", "-fopenmp",
                   "-shared", "-fPIC", "-std=c++17",
                   *self.extra_compile_args(),
                   *self.absolute_sources(), "-o", tmp]
            if verbose:
                logger.info(f"[{self.NAME}] building: {' '.join(cmd)}")
            try:
                subprocess.run(cmd, check=True, capture_output=True, text=True)
                os.replace(tmp, lib)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"native build of {self.NAME} failed:\n{e.stderr}") from e
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
        return lib

    def load(self, verbose=False):
        """Build if needed and return the ctypes CDLL (cached per-process)."""
        if self.NAME in OpBuilder._cache:
            return OpBuilder._cache[self.NAME]
        if not self.is_compatible(verbose=verbose):
            raise RuntimeError(f"op {self.NAME} is not buildable on this host")
        cdll = ctypes.CDLL(self.build(verbose=verbose))
        self._declare(cdll)
        OpBuilder._cache[self.NAME] = cdll
        return cdll

    jit_load = load  # reference API alias

    def _declare(self, cdll):
        """Subclass hook: set argtypes/restype on the loaded functions."""
