"""Native extension loader: builds murmur.cpp with g++ on first use.

Binding is ctypes (no pybind11 in the image); a pure-Python fallback keeps
every feature working when no compiler is available — an order of
magnitude slower, so a failed build is reported, not swallowed. The .so is
cached next to the source and rebuilt when the source is newer.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
import threading
from pathlib import Path
from typing import List, Optional, Sequence

import numpy as np

_HERE = Path(__file__).parent
_SRC = _HERE / "murmur.cpp"
_SO = _HERE / "libsrt_native.so"
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

logger = logging.getLogger("spacy_ray_tpu.native")
# its own level: the commands hold the root logger at ERROR unless --verbose,
# and the one warning here (a build that failed) must reach the operator
logger.setLevel(logging.WARNING)


def _build() -> bool:
    """Compile to a temporary name in the same directory, then rename onto
    the final path: several processes import at once (collate workers,
    fleet children, replicas), and none may load a half-written file."""
    fd, tmp = tempfile.mkstemp(prefix=".libsrt_native.", suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, str(_SRC)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        logger.warning(
            "native hash library did not build (%s: %s)%s — falling back to "
            "the pure-Python murmur hash, about 10x slower on the collate "
            "path",
            type(e).__name__, e,
            ": " + stderr.decode("utf8", "replace").strip() if stderr else "",
        )
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """Return the native lib, building it if needed; None if unavailable."""
    global _LIB, _TRIED
    if _LIB is not None:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            needs_build = (not _SO.exists()) or (
                _SRC.stat().st_mtime > _SO.stat().st_mtime
            )
            if needs_build and not _build():
                return None
            lib = ctypes.CDLL(str(_SO))
            lib.murmur3_u64.restype = ctypes.c_uint64
            lib.murmur3_u64.argtypes = [
                ctypes.c_char_p,
                ctypes.c_int64,
                ctypes.c_uint32,
            ]
            lib.murmur3_u64_batch.restype = None
            lib.murmur3_u64_batch.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint64),
            ]
            _LIB = lib
        except OSError as e:
            logger.warning("native hash library did not load: %s", e)
            _LIB = None
        return _LIB


def available() -> bool:
    return load() is not None


def hash_strings_u64(strings: Sequence[str], seed: int = 0) -> np.ndarray:
    """Batch 64-bit murmur of utf-8 strings. Native when possible."""
    lib = load()
    if lib is None:
        from ..ops.hashing import hash_string_u64

        return np.array(
            [hash_string_u64(s, seed) for s in strings], dtype=np.uint64
        )
    encoded = [s.encode("utf8") for s in strings]
    n = len(encoded)
    offsets = np.zeros(n + 1, dtype=np.int64)
    for i, b in enumerate(encoded):
        offsets[i + 1] = offsets[i] + len(b)
    blob = b"".join(encoded)
    out = np.zeros(n, dtype=np.uint64)
    lib.murmur3_u64_batch(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        seed & 0xFFFFFFFF,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out
