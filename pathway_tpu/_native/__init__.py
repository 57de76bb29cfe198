"""Loader for the C++ host-runtime core (native.cpp).

Compiles ``native.cpp`` with g++ on first import (cached as a .so next to
the source, keyed by a hash of the source and the compiler flags) and
binds it via ctypes.  The build targets the baseline ISA, not the
building host's: the .so is git-ignored but travels with a copied tree,
so it must load on a machine with a different CPU.  The call sites
(models/tokenizer.py, io/fs) have pure-Python fallbacks (the tokenizer's
~130x slower) which warn when they engage.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Iterator

import numpy as np

__all__ = [
    "hash_bytes", "tokenize_batch", "walk_dir", "read_files", "lib",
    "ABI_VERSION",
]

ABI_VERSION = 2

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cpp")
# no fused multiply-add: pw_fs_walk's st_mtime has to be the very float
# os.stat computes (sec + 1e-9 * nsec, rounded twice)
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17", "-ffp-contract=off")


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


class _FsWalk(ctypes.Structure):
    _fields_ = [
        ("n", ctypes.c_int64), ("entries", ctypes.c_int64),
        ("paths_len", ctypes.c_int64), ("paths", ctypes.c_void_p),
        ("mtimes", ctypes.c_void_p), ("sizes", ctypes.c_void_p),
    ]


class _FsRead(ctypes.Structure):
    _fields_ = [
        ("n_read", ctypes.c_int64), ("data_len", ctypes.c_int64),
        ("data", ctypes.c_void_p), ("ends", ctypes.c_void_p),
        ("errs", ctypes.c_void_p),
    ]


lib.pw_fs_walk.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
lib.pw_fs_walk.restype = ctypes.POINTER(_FsWalk)
lib.pw_fs_walk_free.argtypes = [ctypes.POINTER(_FsWalk)]
lib.pw_fs_walk_free.restype = None
lib.pw_fs_read.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64]
lib.pw_fs_read.restype = ctypes.POINTER(_FsRead)
lib.pw_fs_read_free.argtypes = [ctypes.POINTER(_FsRead)]
lib.pw_fs_read_free.restype = None


def _list_at(dtype: str, address: int | None, n: int) -> list:
    """``n`` numbers at ``address`` as Python floats or ints."""
    if not n:
        return []
    return np.frombuffer(
        ctypes.string_at(address, n * np.dtype(dtype).itemsize), dtype
    ).tolist()


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


def walk_dir(root: bytes, pattern: bytes) -> tuple[bytes, list[float], list[int], int]:
    """The regular files under the directory ``root`` (ending with ``/``)
    whose base name matches ``pattern`` (``*`` and ``?`` only), as
    ``glob.glob(root + b"**/" + pattern, recursive=True)`` finds them:
    ``(paths joined by NUL in sorted order, st_mtime of each, st_size of
    each, directory entries read)``; the last is -1 when ``root`` cannot
    be listed (it is a single file, or not there).  One call without the
    interpreter lock; see native.cpp for what exactly it mirrors."""
    res = lib.pw_fs_walk(root, pattern)
    if not res:
        raise MemoryError("pw_fs_walk")
    try:
        w = res.contents
        return (
            ctypes.string_at(w.paths, w.paths_len) if w.paths_len else b"",
            _list_at("f8", w.mtimes, w.n),
            _list_at("i8", w.sizes, w.n),
            w.entries,
        )
    finally:
        lib.pw_fs_walk_free(res)


#: bytes one ``pw_fs_read`` call may hold before it hands back
READ_BUDGET = 64 << 20


def read_files(paths: list[bytes]) -> Iterator[bytes | OSError]:
    """The bytes of each file in turn, or the ``OSError`` that kept them:
    what ``open(path, "rb").read()`` gives.  The files are read ahead
    without the interpreter lock, ``READ_BUDGET`` bytes a call, so the
    calls grow with the bytes read and not with the files."""
    done = 0
    while done < len(paths):
        rest = paths[done:] if done else paths
        res = lib.pw_fs_read(b"\0".join(rest) + b"\0", len(rest), READ_BUDGET)
        if not res:
            raise MemoryError("pw_fs_read")
        try:
            r = res.contents
            ends = _list_at("i8", r.ends, r.n_read)
            errs = _list_at("i4", r.errs, r.n_read)
            # a slice of the array is a copy: the only one Python makes
            data = (ctypes.c_char * r.data_len).from_address(r.data or 0) if r.data_len else b""
            chunk: list[bytes | OSError] = []
            start = 0
            for path, end, err in zip(rest, ends, errs):
                chunk.append(
                    OSError(err, os.strerror(err), os.fsdecode(path))
                    if err else data[start:end]
                )
                start = end
        finally:
            lib.pw_fs_read_free(res)
        done += len(chunk)
        yield from chunk
