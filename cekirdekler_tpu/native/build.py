"""Builder + ctypes loader for the native host runtime library.

The reference ships its native layer as a prebuilt DLL; ours is built
from ``kutuphane_tpu.cpp`` — the only tracked artifact — with the system
toolchain on first use.  The shared object is named after a hash of the
source, so "is the build current?" is answered by content, never by file
mtimes (a copied or freshly checked-out tree has arbitrary ones).
Thread-safe.  If the build or load fails, :func:`load` returns ``None``
and callers use their pure-Python host paths — with one warning, and
:func:`runtime` naming which runtime is active and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import re
import subprocess
import threading
import warnings
from pathlib import Path

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "kutuphane_tpu.cpp"
_ABI = 2

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_error: str | None = None


def _lib_path() -> Path:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:12]
    return _HERE / f"libkutuphane_tpu.{digest}.so"


def _compile(out: Path) -> None:
    tmp = out.with_suffix(f".tmp{threading.get_ident()}.so")
    cmd = [
        "g++",
        "-O2",
        "-shared",
        "-fPIC",
        "-std=c++17",
        "-fvisibility=hidden",
        str(_SRC),
        "-o",
        str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        tmp.replace(out)  # atomic: a racing process never loads a half file
    finally:
        tmp.unlink(missing_ok=True)
    # builds of OLDER sources only — never a racing process's tmp file
    for stale in _HERE.glob("libkutuphane_tpu.*.so"):
        if stale != out and re.fullmatch(
                r"libkutuphane_tpu\.[0-9a-f]{12}\.so", stale.name):
            stale.unlink(missing_ok=True)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64 = ctypes.c_int64
    p = ctypes.c_void_p
    lib.ck_sizeOf.argtypes = [ctypes.c_int]
    lib.ck_sizeOf.restype = ctypes.c_int
    lib.ck_createArray.argtypes = [i64, i64]
    lib.ck_createArray.restype = p
    lib.ck_alignedArrHead.argtypes = [p, i64]
    lib.ck_alignedArrHead.restype = p
    lib.ck_deleteArray.argtypes = [p, i64, i64]
    lib.ck_deleteArray.restype = None
    lib.ck_copyMemory.argtypes = [p, p, i64]
    lib.ck_copyMemory.restype = None
    lib.ck_fillMemory.argtypes = [p, ctypes.c_int, i64]
    lib.ck_fillMemory.restype = None
    lib.ck_liveAllocations.argtypes = []
    lib.ck_liveAllocations.restype = i64
    lib.ck_liveBytes.argtypes = []
    lib.ck_liveBytes.restype = i64
    for name in (
        "ck_createMarkerCounter",
        "ck_abiVersion",
    ):
        getattr(lib, name).argtypes = []
        getattr(lib, name).restype = i64
    for name in ("ck_deleteMarkerCounter", "ck_addMarker", "ck_markerReached", "ck_resetMarkerCounter"):
        getattr(lib, name).argtypes = [i64]
        getattr(lib, name).restype = None
    for name in ("ck_markersAdded", "ck_markersReached", "ck_markersRemaining"):
        getattr(lib, name).argtypes = [i64]
        getattr(lib, name).restype = i64
    # events (ClEvent/ClUserEvent parity)
    lib.ck_eventCreate.argtypes = []
    lib.ck_eventCreate.restype = i64
    for name in ("ck_eventDelete", "ck_eventTrigger", "ck_eventIncrement", "ck_eventDecrement"):
        getattr(lib, name).argtypes = [i64]
        getattr(lib, name).restype = None
    lib.ck_eventFired.argtypes = [i64]
    lib.ck_eventFired.restype = ctypes.c_int
    lib.ck_eventWait.argtypes = [i64, i64]
    lib.ck_eventWait.restype = ctypes.c_int
    lib.ck_eventPending.argtypes = [i64]
    lib.ck_eventPending.restype = i64
    # async copy engine
    lib.ck_copyEngineStart.argtypes = [ctypes.c_int]
    lib.ck_copyEngineStart.restype = None
    lib.ck_copyEngineThreads.argtypes = []
    lib.ck_copyEngineThreads.restype = ctypes.c_int
    lib.ck_copyEngineQueued.argtypes = []
    lib.ck_copyEngineQueued.restype = i64
    lib.ck_copyAsync.argtypes = [p, p, i64, i64]
    lib.ck_copyAsync.restype = None
    lib.ck_copyParallel.argtypes = [p, p, i64, ctypes.c_int]
    lib.ck_copyParallel.restype = None
    return lib


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable
    — the failure is warned once and kept for :func:`runtime`."""
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    with _lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            path = _lib_path()
            if not path.exists():
                _compile(path)
            lib = ctypes.CDLL(str(path))
            if lib.ck_abiVersion() != _ABI:
                raise OSError(f"ABI {lib.ck_abiVersion()} != {_ABI}")
            _lib = _bind(lib)
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            _load_error = (f"{type(e).__name__}: {e} "
                           f"{detail.decode(errors='replace')[-300:]}").strip()
            warnings.warn(
                "cekirdekler_tpu native host runtime unavailable — using "
                f"the pure-Python host paths ({_load_error})",
                RuntimeWarning, stacklevel=2)
        return _lib


def available() -> bool:
    return load() is not None


def runtime() -> str:
    """Which host runtime is active: ``"native (<file>, abi N)"`` or
    ``"python (<why the native build is unavailable>)"``."""
    if load() is not None:
        return f"native ({_lib_path().name}, abi {_ABI})"
    return f"python ({_load_error})"
