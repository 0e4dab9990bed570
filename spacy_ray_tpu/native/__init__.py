"""Native extension loader: builds murmur.cpp and oracle.cpp into one library
with g++ on first use.

Binding is ctypes (no pybind11 in the image); a pure-Python fallback keeps
every feature working when no compiler is available — an order of
magnitude slower and more, so a failed build is reported, not swallowed. The
.so is cached next to the sources and rebuilt when either is newer, or when
it lacks a symbol the sources have (an ignored .so of an older tree, copied
with the tree).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
import threading
from pathlib import Path
from typing import Any, Optional, Sequence, Tuple

import numpy as np

_HERE = Path(__file__).parent
_SOURCES: Tuple[Path, ...] = (_HERE / "murmur.cpp", _HERE / "oracle.cpp")
_SO = _HERE / "libsrt_native.so"
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_WHY_MISSING = ""  # once tried and not loaded: the reason, for the reports

logger = logging.getLogger("spacy_ray_tpu.native")
# its own level: the commands hold the root logger at ERROR unless --verbose,
# and the one warning here (a build that failed) must reach the operator
logger.setLevel(logging.WARNING)

# oracle.cpp's statuses below zero; DECLINED is what arc_eager_oracle hands
# back for the second (the caller then runs the Python state machine)
_UNUSABLE = -1
DECLINED = object()
_NEWEST_EXPORT = b"arc_eager_replay"


def _build() -> bool:
    """Compile to a temporary name in the same directory, then rename onto
    the final path: several processes import at once (collate workers,
    fleet children, replicas), and none may load a half-written file."""
    global _WHY_MISSING
    fd, tmp = tempfile.mkstemp(prefix=".libsrt_native.", suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, *map(str, _SOURCES)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        _WHY_MISSING = (
            "no g++" if isinstance(e, FileNotFoundError)
            else f"g++ failed: {type(e).__name__}"
        )
        logger.warning(
            "native library did not build (%s: %s)%s — falling back to the "
            "pure-Python murmur hash and parser oracle, 10x and more slower "
            "on the collate path",
            type(e).__name__, e,
            ": " + stderr.decode("utf8", "replace").strip() if stderr else "",
        )
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _stale() -> bool:
    if not _SO.exists():
        return True
    built = _SO.stat().st_mtime
    if any(src.stat().st_mtime > built for src in _SOURCES):
        return True
    # newer than both sources and yet an older tree's (an ignored file, copied
    # with the tree): it does not name the newest export. Read off the file:
    # once loaded, the loader hands back the same mapping for the same path
    return _NEWEST_EXPORT not in _SO.read_bytes()


def _declare(lib: ctypes.CDLL) -> None:
    """argtypes and restype of every export; AttributeError names one the
    library lacks."""
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
    # addresses as integers (ndarray.ctypes.data): a document a call, so the
    # call's own cost counts
    lib.arc_eager_gold_oracle.restype = ctypes.c_int64
    lib.arc_eager_gold_oracle.argtypes = [
        ctypes.c_void_p,  # heads [n] int64
        ctypes.c_void_p,  # label_ids [n] int64
        ctypes.c_int64,  # n
        ctypes.c_int64,  # n_labels
        ctypes.c_void_p,  # actions [cap] int64
        ctypes.c_void_p,  # feats [cap, 12] int64
        ctypes.c_void_p,  # valid [cap, 2 + 2 * n_labels] bool
        ctypes.c_int64,  # cap
    ]
    lib.arc_eager_replay.restype = ctypes.c_int64
    lib.arc_eager_replay.argtypes = [
        ctypes.c_void_p,  # actions [steps] int32
        ctypes.c_int64,  # steps
        ctypes.c_int64,  # n
        ctypes.c_int64,  # n_labels
        ctypes.c_void_p,  # feats [steps, 12] int64
        ctypes.c_void_p,  # valid [steps, 2 + 2 * n_labels] bool
    ]


def load() -> Optional[ctypes.CDLL]:
    """Return the native lib, building it if needed; None if unavailable."""
    global _LIB, _TRIED, _WHY_MISSING
    if _LIB is not None:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            if _stale() and not _build():
                return None
            lib = ctypes.CDLL(str(_SO))
            _declare(lib)
            _LIB = lib
        except (OSError, AttributeError) as e:
            _WHY_MISSING = f"did not load: {type(e).__name__}"
            logger.warning("native library did not load: %s", e)
            _LIB = None
        return _LIB


def available() -> bool:
    return load() is not None


def why_missing() -> str:
    """Why ``load()`` gave None ("" while it has not, or before a first try)."""
    return _WHY_MISSING


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


def arc_eager_oracle(
    lib: ctypes.CDLL, heads: Sequence[int], label_ids: Sequence[int], n_labels: int
) -> Any:
    """oracle.cpp for one document: ``pipeline.transition.gold_oracle``'s
    three arrays (int64 [S], int64 [S, 12], bool [S, 2 + 2 * n_labels]), None
    for an unusable tree, or ``DECLINED`` where the Python state machine has
    to answer (arguments it would index or raise on differently)."""
    heads_a = np.ascontiguousarray(heads, dtype=np.int64)
    labels_a = np.ascontiguousarray(label_ids, dtype=np.int64)
    if heads_a.ndim != 1 or labels_a.ndim != 1 or len(labels_a) < len(heads_a):
        return DECLINED
    n = len(heads_a)
    n_labels = int(n_labels)
    # every token is pushed once and popped once: 2n steps where the machine
    # ends at all (the Python's own bound, 4n + 4, is never the one that binds)
    cap = 2 * n
    actions = np.empty(cap, dtype=np.int64)
    feats = np.empty((cap, 12), dtype=np.int64)
    valid = np.empty((cap, 2 + 2 * max(n_labels, 0)), dtype=np.bool_)
    steps = lib.arc_eager_gold_oracle(
        heads_a.ctypes.data, labels_a.ctypes.data, n, n_labels,
        actions.ctypes.data, feats.ctypes.data, valid.ctypes.data, cap,
    )
    if steps == _UNUSABLE:
        return None
    if steps < 0:
        return DECLINED
    return actions[:steps], feats[:steps], valid[:steps]


def arc_eager_replay(
    lib: ctypes.CDLL, actions: np.ndarray, n_words: int, n_labels: int
) -> Any:
    """oracle.cpp's second export: the state rows (feats int64 [S, 12], valid
    bool [S, 2 + 2 * n_labels]) before each of a document's ``actions``, or
    None where they are not a whole run of the machine over ``n_words``."""
    actions = np.ascontiguousarray(actions, dtype=np.int32)
    if actions.ndim != 1:
        return None
    steps = len(actions)
    n_labels = max(int(n_labels), 0)
    feats = np.empty((steps, 12), dtype=np.int64)
    valid = np.empty((steps, 2 + 2 * n_labels), dtype=np.bool_)
    done = lib.arc_eager_replay(
        actions.ctypes.data, steps, int(n_words), n_labels,
        feats.ctypes.data, valid.ctypes.data,
    )
    return (feats, valid) if done == steps else None
