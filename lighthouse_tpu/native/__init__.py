"""Native (C++) runtime components and their build machinery.

The reference links native code for its storage and crypto hot paths
(SURVEY.md §2.7: leveldb C++, blst asm, c-kzg C). Here the TPU compute path
is JAX/Pallas; the host runtime pieces that must not be Python are built
from C++ sources in `src/` and loaded via ctypes.

`load(name)` compiles `src/<name>.cpp` on first use (g++ is baked into the
image) and returns the ctypes CDLL. Each build is keyed by a hash of the
source, the compiler flags and the host's CPU flags, and the key is part
of the artifact's file name (`build/lib<name>-<key>.so`): a checkout copied
to another host (the chip machine) never loads a `-march=native` artifact
built for this one, where a missing instruction would die with SIGILL.
"""

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src")
_BUILD = os.path.join(_HERE, "build")
_lock = threading.Lock()
_cache = {}

# -march=native unlocks SHA-NI/AVX paths where guarded by #ifdef in the
# sources; the host's CPU flags are part of the build key for that reason.
_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-march=native"]


def _cpu_flags() -> str:
    """The host's CPU feature list (what -march=native resolves from)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.machine()


def build_key(src: str) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_flags().encode())
    return h.hexdigest()[:16]


def load(name: str) -> ctypes.CDLL:
    with _lock:
        if name in _cache:
            return _cache[name]
        src = os.path.join(_SRC, f"{name}.cpp")
        out = os.path.join(_BUILD, f"lib{name}-{build_key(src)}.so")
        if not os.path.exists(out):
            os.makedirs(_BUILD, exist_ok=True)
            tmp = f"{out}.{os.getpid()}.tmp"   # concurrent builders
            subprocess.run(["g++", *_FLAGS, "-o", tmp, src],
                           check=True, capture_output=True)
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
        _cache[name] = lib
        return lib
