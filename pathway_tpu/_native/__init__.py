"""Loader for the C++ host-runtime core (native.cpp).

Compiles ``native.cpp`` with g++ on first import (cached as a .so next to
the source, keyed by a hash of the source and the compiler flags) and
binds it via ctypes.  The build targets the baseline ISA, not the
building host's: the .so is git-ignored but travels with a copied tree,
so it must load on a machine with a different CPU.  The call site
(models/tokenizer.py) has a pure-Python fallback, ~130x slower, which
warns when it engages.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile

import numpy as np

__all__ = ["hash_bytes", "tokenize_batch", "lib", "ABI_VERSION"]

ABI_VERSION = 1

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cpp")
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def _build() -> str:
    with open(_SRC, "rb") as f:
        src = f.read()
    tag = hashlib.blake2b(
        src + str(ABI_VERSION).encode() + " ".join(_CXXFLAGS).encode(),
        digest_size=8,
    ).hexdigest()
    so_path = os.path.join(_HERE, f"_pathway_native_{tag}.so")
    if os.path.exists(so_path):
        return so_path
    # build in a temp file, then atomically move into place (concurrent
    # imports may race)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", *_CXXFLAGS, "-o", tmp, _SRC],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, so_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # drop stale builds
    for name in os.listdir(_HERE):
        if name.startswith("_pathway_native_") and name != os.path.basename(so_path):
            try:
                os.unlink(os.path.join(_HERE, name))
            except OSError:
                pass
    return so_path


lib = ctypes.CDLL(_build())

lib.pw_native_abi_version.restype = ctypes.c_int
if lib.pw_native_abi_version() != ABI_VERSION:  # pragma: no cover
    raise ImportError("stale pathway native library")

lib.pw_blake2b128.argtypes = [
    ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p
]
lib.pw_tokenize_batch.argtypes = [
    ctypes.POINTER(ctypes.c_char_p),      # texts
    ctypes.POINTER(ctypes.c_int64),       # text_lens
    ctypes.c_int64,                       # n
    ctypes.POINTER(ctypes.c_char_p),      # pairs (nullable)
    ctypes.POINTER(ctypes.c_int64),       # pair_lens (nullable)
    ctypes.c_int64,                       # max_length
    ctypes.c_int64,                       # vocab_size
    ctypes.c_int,                         # lowercase
    ctypes.c_void_p,                      # out_ids
    ctypes.c_void_p,                      # out_mask
]


def hash_bytes(data: bytes) -> int:
    """128-bit BLAKE2b of ``data`` as an int (little-endian), identical to
    ``int.from_bytes(hashlib.blake2b(data, digest_size=16).digest(),
    "little")``."""
    out = ctypes.create_string_buffer(16)
    lib.pw_blake2b128(data, len(data), out)
    return int.from_bytes(out.raw, "little")


def tokenize_batch(
    texts: list[bytes],
    max_length: int,
    vocab_size: int,
    lowercase: bool = True,
    pairs: list[bytes] | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Batch hashing-tokenizer encode: returns (ids, mask), both
    int32[n, max_length]."""
    n = len(texts)
    ids = np.zeros((n, max_length), dtype=np.int32)
    mask = np.zeros((n, max_length), dtype=np.int32)
    if n == 0:
        return ids, mask
    text_arr = (ctypes.c_char_p * n)(*texts)
    len_arr = (ctypes.c_int64 * n)(*[len(t) for t in texts])
    if pairs is not None:
        pair_arr = (ctypes.c_char_p * n)(*pairs)
        plen_arr = (ctypes.c_int64 * n)(*[len(p) for p in pairs])
    else:
        pair_arr = None
        plen_arr = None
    lib.pw_tokenize_batch(
        text_arr, len_arr, n,
        pair_arr, plen_arr,
        max_length, vocab_size, int(lowercase),
        ids.ctypes.data_as(ctypes.c_void_p),
        mask.ctypes.data_as(ctypes.c_void_p),
    )
    return ids, mask
