"""ctypes binding to the native threaded dependency engine
(native/engine.cc -> libengine.so).

Reference analogue: the C API surface of the dependency engine
(``include/mxnet/engine.h:95-280``) that every subsystem schedules
through.  Here the native engine schedules *host-side* tasks (IO,
checkpoint, transport) — device work is XLA/PJRT's job — but the
dependency protocol (const/mutable vars, serialized writes, parallel
reads, WaitForVar/WaitForAll) is the same observable contract
(SURVEY §3.3).

``lib()`` returns None only where no build was asked for
(``MXNET_TPU_BUILD_NATIVE=0`` / no ``native/`` sources — see the package
docstring) and the Python facade then runs tasks synchronously inline;
a failed build raises.
"""
from __future__ import annotations

import ctypes

from . import load_shared

_lib = None
_tried = False

TASK_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


def lib():
    """The loaded CDLL, or None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    l = load_shared("libengine.so", required_symbol="MXEngineFreeAsync")
    if l is None:
        return None
    l.MXEngineCreate.restype = ctypes.c_void_p
    l.MXEngineCreate.argtypes = [ctypes.c_int, ctypes.c_int]
    l.MXEngineFree.restype = None
    l.MXEngineFree.argtypes = [ctypes.c_void_p]
    l.MXEngineFreeAsync.restype = None
    l.MXEngineFreeAsync.argtypes = [ctypes.c_void_p]
    l.MXEngineNewVariable.restype = ctypes.c_int64
    l.MXEngineNewVariable.argtypes = [ctypes.c_void_p]
    l.MXEngineDeleteVariable.restype = None
    l.MXEngineDeleteVariable.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    l.MXEnginePushAsync.restype = None
    l.MXEnginePushAsync.argtypes = [
        ctypes.c_void_p, TASK_FN, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int]
    l.MXEngineWaitForVar.restype = None
    l.MXEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    l.MXEngineWaitForAll.restype = None
    l.MXEngineWaitForAll.argtypes = [ctypes.c_void_p]
    l.MXEnginePendingTasks.restype = ctypes.c_int
    l.MXEnginePendingTasks.argtypes = [ctypes.c_void_p]
    l.MXEngineSetSync.restype = None
    l.MXEngineSetSync.argtypes = [ctypes.c_void_p, ctypes.c_int]
    _lib = l
    return _lib


def available():
    return lib() is not None
