"""paddle.utils.cpp_extension (reference:
python/paddle/utils/cpp_extension/ — CppExtension/CUDAExtension setup
helpers + JIT ``load`` for custom C++ operators).

TPU-native design: device compute belongs to XLA/Pallas — a custom C++
op cannot run inside a TPU program, so custom native code here is
HOST-side: data-pipeline
stages, CPU pre/post-processing, tokenizers.  ``load`` compiles the
sources with g++ into a shared library (same toolchain as csrc/, no
pybind11 — plain ``extern "C"`` symbols over ctypes) and returns a
handle exposing the exported functions.  On CPU backends the loaded
functions can also ride ``static.py_func`` into a traced graph; the
eager path works everywhere.

CUDAExtension maps to CppExtension with a one-time warning (no CUDA
toolchain on a TPU host); BuildExtension is the setuptools command the
reference's setup(...) flow expects.
"""
import ctypes
import hashlib
import os
import subprocess
import warnings

__all__ = ["CppExtension", "CUDAExtension", "BuildExtension", "load",
           "get_build_directory"]


def get_build_directory(verbose=False):
    """reference: paddle.utils.cpp_extension.get_build_directory."""
    root = os.environ.get("PADDLE_EXTENSION_DIR",
                          os.path.join(os.path.expanduser("~"), ".cache",
                                       "paddle_tpu_extensions"))
    os.makedirs(root, exist_ok=True)
    if verbose:
        print(f"build directory: {root}")
    return root


def CppExtension(sources, *args, **kwargs):
    """setuptools.Extension for custom host-side C++ ops."""
    from setuptools import Extension
    name = kwargs.pop("name", "paddle_tpu_custom_ext")
    include_dirs = list(kwargs.pop("include_dirs", []))
    from .. import sysconfig
    include_dirs.append(sysconfig.get_include())
    return Extension(name, sources, *args, include_dirs=include_dirs,
                     language="c++", **kwargs)


def CUDAExtension(sources, *args, **kwargs):
    warnings.warn(
        "CUDAExtension: no CUDA toolchain on a TPU host — building as a "
        "host-side CppExtension (device compute belongs to XLA/Pallas; "
        "write a Pallas kernel for on-chip custom ops)", stacklevel=2)
    return CppExtension(sources, *args, **kwargs)


class BuildExtension:
    """setuptools build_ext command shim (reference keeps custom compile
    flags per-compiler; g++ is the only compiler here)."""

    @staticmethod
    def with_options(**options):
        from setuptools.command.build_ext import build_ext

        class _Cmd(build_ext):
            def build_extensions(self):
                for ext in self.extensions:
                    ext.extra_compile_args = list(
                        ext.extra_compile_args or []) + ["-std=c++17",
                                                         "-O2", "-fPIC"]
                super().build_extensions()
        return _Cmd

    def __new__(cls, *args, **kwargs):
        return cls.with_options()(*args, **kwargs)


class _LoadedExtension:
    """Handle over the compiled shared library: attribute access returns
    the ctypes symbols; callers declare argtypes/restype as needed (the
    reference returns a python module of generated wrappers — here the
    C ABI is the contract, matching framework/native.py's style)."""

    def __init__(self, name, path):
        self.__name__ = name
        self._path = path
        self._lib = ctypes.CDLL(path)

    def __getattr__(self, item):
        try:
            return getattr(self._lib, item)
        except AttributeError:
            raise AttributeError(
                f"extension {self.__name__!r} has no exported symbol "
                f"{item!r} (symbols must be extern \"C\")")


def load(name, sources, extra_cxx_cflags=None, extra_cuda_cflags=None,
         extra_ldflags=None, extra_include_paths=None,
         build_directory=None, interpreter=None, verbose=False,
         extra_cxx_flags=None):
    """JIT-compile custom C++ sources into a loadable extension
    (reference: paddle.utils.cpp_extension.load).

    Returns a handle whose attributes are the library's ``extern "C"``
    symbols (ctypes).  Rebuilds only when sources/flags change (content
    hash in the artifact name)."""
    if extra_cuda_cflags:
        warnings.warn("extra_cuda_cflags ignored: host-only C++ build "
                      "(see CUDAExtension)", stacklevel=2)
    # the reference spells it extra_cxx_cflags; accept both
    extra_cxx_cflags = extra_cxx_cflags or extra_cxx_flags
    build_dir = build_directory or get_build_directory()
    srcs = [os.path.abspath(s) for s in sources]
    flags = ["-std=c++17", "-O2", "-shared", "-fPIC"]
    flags += list(extra_cxx_cflags or [])
    from .. import sysconfig
    includes = [sysconfig.get_include()] + list(extra_include_paths or [])
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as fh:
            h.update(fh.read())
    h.update(" ".join(flags).encode())
    h.update(" ".join(list(extra_ldflags or [])).encode())
    h.update(" ".join(includes).encode())
    tag = h.hexdigest()[:12]
    out = os.path.join(build_dir, f"{name}-{tag}.so")
    if not os.path.exists(out):
        cmd = (["g++"] + flags + [f"-I{i}" for i in includes]
               + srcs + ["-o", out + ".tmp"]
               + list(extra_ldflags or []))
        if verbose:
            print("compiling:", " ".join(cmd))
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"cpp_extension.load({name!r}) failed:\n{proc.stderr}")
        os.replace(out + ".tmp", out)  # atomic vs concurrent builders
    return _LoadedExtension(name, out)
