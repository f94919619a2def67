"""Native op build system — rebuild of op_builder/builder.py:81,205,217.

The reference JIT-compiles CUDA extensions through torch's ninja wrapper;
here each op is a plain C++ shared library compiled with g++ straight from
deepspeed_tpu/csrc/, cached next to the sources, and loaded with ctypes.
No nvcc, no compute-capability matrix — the TPU compute path is Pallas; this
covers host-side ops (SIMD optimizer, async IO).
"""

import ctypes
import hashlib
import os
import subprocess
import threading

from deepspeed_tpu.utils.logging import logger

CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc")
BUILD_DIR = os.path.join(CSRC, "build")

_lock = threading.Lock()
_cache = {}


def loaded_ops():
    """Names of the native libraries this process has loaded."""
    return sorted(_cache)


def _host_cpu_flags():
    """The host CPU's feature flags: ``-march=native`` bakes them into the
    library, so a build made on another machine must not be reused."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return ""


class OpBuilder:
    """One source → one .so. ``load()`` compiles on first use (the
    reference's jit_load path, builder.py:217) and returns the ctypes CDLL.
    """

    def __init__(self, name, sources, extra_flags=()):
        self.name = name
        self.sources = sources
        self.extra_flags = list(extra_flags)

    def absolute_sources(self):
        return [os.path.join(CSRC, s) for s in self.sources]

    def so_path(self):
        suffix = "_tsan" if self._tsan() else ""
        return os.path.join(BUILD_DIR, f"lib{self.name}{suffix}.so")

    @staticmethod
    def _tsan():
        """DS_BUILD_TSAN=1 builds the host libraries under ThreadSanitizer —
        the concurrency guard rail SURVEY §5.2 calls for on the swap/aio
        thread pools (the reference has no sanitizer story at all). TSAN
        builds cache separately so switching modes doesn't thrash.

        Running requires the runtime preloaded (dlopen'ing a TSAN .so into
        a plain python hits the static-TLS limit):

            LD_PRELOAD=$(g++ -print-file-name=libtsan.so) \\
                DS_BUILD_TSAN=1 python -m pytest tests/test_offload.py
        """
        return os.environ.get("DS_BUILD_TSAN", "") == "1"

    def is_compatible(self):
        from shutil import which
        return which("g++") is not None

    def flags(self):
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
               "-march=native", "-fopenmp"]
        if self._tsan():
            cmd += ["-fsanitize=thread", "-g", "-O1"]
        return cmd + self.extra_flags

    def command(self, out=None):
        return (self.flags() + self.absolute_sources()
                + ["-o", out or self.so_path()])

    def build_key(self):
        """Hash of everything the library depends on: source bytes, the
        compile flags, and the host CPU (``-march=native``)."""
        h = hashlib.sha256()
        for part in self.flags() + [_host_cpu_flags()]:
            h.update(part.encode() + b"\0")
        for src in self.absolute_sources():
            with open(src, "rb") as f:
                h.update(f.read())
        return h.hexdigest()

    def key_path(self):
        return self.so_path() + ".key"

    def needs_build(self):
        """True unless the cached library was built from these sources with
        these flags on this CPU. A library without its key file (one copied
        in from elsewhere) is rebuilt."""
        try:
            with open(self.key_path()) as f:
                return f.read() != self.build_key() \
                    or not os.path.exists(self.so_path())
        except OSError:
            return True

    def build(self):
        os.makedirs(BUILD_DIR, exist_ok=True)
        # build beside the target and rename: a concurrent process never
        # loads a half-written library
        tmp = f"{self.so_path()}.{os.getpid()}.tmp"
        cmd = self.command(out=tmp)
        logger.info(f"[op_builder] building {self.name}: {' '.join(cmd)}")
        try:
            subprocess.check_output(cmd, stderr=subprocess.STDOUT)
        except subprocess.CalledProcessError as e:
            # retry without -march=native (portable fallback)
            cmd = [c for c in cmd if c != "-march=native"]
            try:
                subprocess.check_output(cmd, stderr=subprocess.STDOUT)
            except subprocess.CalledProcessError as e2:
                raise RuntimeError(
                    f"failed to build {self.name}: {e2.output.decode()}") from e
        os.replace(tmp, self.so_path())
        with open(f"{self.key_path()}.{os.getpid()}.tmp", "w") as f:
            f.write(self.build_key())
        os.replace(f.name, self.key_path())

    def load(self):
        with _lock:
            if self.name in _cache:
                return _cache[self.name]
            if not self.is_compatible():
                raise RuntimeError("no C++ compiler available")
            if self.needs_build():
                self.build()
            lib = ctypes.CDLL(self.so_path())
            _cache[self.name] = lib
            return lib


class CPUAdamBuilder(OpBuilder):
    def __init__(self):
        super().__init__("cpu_adam", ["cpu_adam.cpp"])


class AsyncIOBuilder(OpBuilder):
    def __init__(self):
        super().__init__("aio", ["aio.cpp"], extra_flags=["-pthread"])


ALL_OPS = {
    "cpu_adam": CPUAdamBuilder,
    "async_io": AsyncIOBuilder,
}
