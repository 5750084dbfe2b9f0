"""ctypes bindings to the native runtime (native/ → lib*.so).

Reference analogue: the ctypes bridge in ``python/mxnet/base.py`` loading
``libmxnet.so``.  Here the native surface is split per subsystem
(RecordIO codec, threaded image loader, dependency engine; SURVEY §2.1).

The shared objects are build products (git-ignored): a checkout builds
each one from ``native/`` on first use.  A build that fails raises with
make's output — callers never degrade to their pure-python twins
because a compile broke.  The pure-python implementations are used only
where no build was asked for: ``MXNET_TPU_BUILD_NATIVE=0`` with the
library absent, or an install that ships no ``native/`` sources.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

from ..base import MXNetError

_DIR = os.path.dirname(os.path.abspath(__file__))
_NATIVE_SRC = os.path.join(os.path.dirname(os.path.dirname(_DIR)), "native")
_LOADED = {}              # so_name -> CDLL | None (memoized, incl. misses)


def load_shared(so_name, required_symbol=None):
    """Load ``so_name`` from the package dir, building it with the
    in-image toolchain on first miss (serialized via a per-target lock
    file so concurrent workers don't race the same ``make``).  Returns a
    CDLL, or None when no build was asked for (see the module
    docstring); raises ``MXNetError`` when the build or the load fails.
    Memoized per name.

    ``required_symbol`` guards against a stale prebuilt library: when
    the loaded object lacks the symbol, it is rebuilt once from source
    and reloaded (gitignored .so files can predate an ABI addition).
    """
    if so_name in _LOADED:
        return _LOADED[so_name]
    lib = _load_uncached(so_name)
    if lib is not None and required_symbol is not None and \
            not hasattr(lib, required_symbol):
        lib = _load_uncached(so_name, rebuild=True)
        if lib is None or not hasattr(lib, required_symbol):
            raise MXNetError("%s lacks symbol %s even after a rebuild "
                             "from native/" % (so_name, required_symbol))
    _LOADED[so_name] = lib
    return lib


def _load_uncached(so_name, rebuild=False):
    so_path = os.path.join(_DIR, so_name)
    can_build = os.path.isdir(_NATIVE_SRC) and \
        os.environ.get("MXNET_TPU_BUILD_NATIVE", "1") == "1"
    if rebuild and can_build and os.path.exists(so_path):
        os.remove(so_path)
    if not os.path.exists(so_path):
        if not can_build:
            return None
        _build(so_path)
    try:
        return ctypes.CDLL(so_path)
    except OSError:
        # corrupt or ABI-incompatible artifact: rebuild once from source
        if rebuild or not can_build:
            raise
        return _load_uncached(so_name, rebuild=True)


def _build(so_path):
    import fcntl
    import logging
    logging.getLogger("mxnet_tpu").info(
        "building %s (one-time; set MXNET_TPU_BUILD_NATIVE=0 to skip)",
        os.path.basename(so_path))
    with open(so_path + ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.exists(so_path):      # another process built it
            return
        proc = subprocess.run(
            ["make", "-C", _NATIVE_SRC,
             os.path.relpath(so_path, _NATIVE_SRC)],
            capture_output=True, text=True, timeout=300)
    if proc.returncode or not os.path.exists(so_path):
        raise MXNetError(
            "building %s from %s failed (make rc=%d):\n%s\n%s"
            % (os.path.basename(so_path), _NATIVE_SRC, proc.returncode,
               proc.stdout[-2000:], proc.stderr[-4000:]))


_lib = None
_tried = False


def lib():
    """The RecordIO codec CDLL, or None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    l = load_shared("librecordio.so")
    if l is None:
        return None
    l.MXRIOWriterCreate.restype = ctypes.c_void_p
    l.MXRIOWriterCreate.argtypes = [ctypes.c_char_p]
    l.MXRIOWrite.restype = ctypes.c_int
    l.MXRIOWrite.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                             ctypes.c_uint64]
    l.MXRIOWriterTell.restype = ctypes.c_int64
    l.MXRIOWriterTell.argtypes = [ctypes.c_void_p]
    l.MXRIOWriterFree.restype = None
    l.MXRIOWriterFree.argtypes = [ctypes.c_void_p]
    l.MXRIOReaderCreate.restype = ctypes.c_void_p
    l.MXRIOReaderCreate.argtypes = [ctypes.c_char_p]
    l.MXRIORead.restype = ctypes.c_int
    l.MXRIORead.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_char_p),
                            ctypes.POINTER(ctypes.c_uint64)]
    l.MXRIOReaderTell.restype = ctypes.c_int64
    l.MXRIOReaderTell.argtypes = [ctypes.c_void_p]
    l.MXRIOReaderSeek.restype = ctypes.c_int
    l.MXRIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    l.MXRIOReaderFree.restype = None
    l.MXRIOReaderFree.argtypes = [ctypes.c_void_p]
    _lib = l
    return _lib


def available():
    return lib() is not None
