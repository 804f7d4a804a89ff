"""Build-and-cache for the native C++ components.

Compiles <name>.cpp in this directory on first use into
`_<name>-<key>.so` next to it, where <key> is a hash of the source and
the compile flags: a binary is used exactly when it was built from this
source with these flags, whatever the files' mtimes say, and a fresh
checkout (the binaries are not tracked) builds its own. No network, no
external build system — just g++ (baked into the image).

Several processes may build at once (test workers on a fresh checkout):
each compiles to a temp name of its own and renames it into place, so a
reader only ever sees a whole binary.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache: dict = {}


class NativeBuildError(RuntimeError):
    pass


_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-fno-plt", "-pthread"]


def load(name: str) -> ctypes.CDLL:
    with _lock:
        if name in _cache:
            return _cache[name]
        src = os.path.join(_HERE, f"{name}.cpp")
        with open(src, "rb") as f:
            key = hashlib.sha256(
                " ".join(_FLAGS).encode() + b"\0" + f.read()
            ).hexdigest()[:16]
        so = os.path.join(_HERE, f"_{name}-{key}.so")
        if not os.path.exists(so):
            tmp = f"{so}.{os.getpid()}.build"
            proc = subprocess.run(["g++", *_FLAGS, "-o", tmp, src],
                                  capture_output=True, text=True)
            if proc.returncode != 0:
                raise NativeBuildError(
                    f"g++ failed for {name}:\n{proc.stderr[-4000:]}")
            os.replace(tmp, so)
            # binaries of older sources/flags are garbage now
            for old in glob.glob(os.path.join(_HERE, f"_{name}-*.so")):
                if old != so:
                    try:
                        os.unlink(old)
                    except OSError:
                        pass
        lib = ctypes.CDLL(so)
        _cache[name] = lib
        return lib
