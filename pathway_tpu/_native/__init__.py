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
    "hash_bytes", "tokenize_batch", "list_dir", "stat_files", "read_files",
    "lib", "ABI_VERSION",
]

ABI_VERSION = 3

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cpp")
# no fused multiply-add: pw_fs_list's st_mtime has to be the very float
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


class _FsList(ctypes.Structure):
    _fields_ = [
        ("n", ctypes.c_int64), ("entries", ctypes.c_int64),
        ("stats", ctypes.c_int64),
        ("paths_len", ctypes.c_int64), ("paths", ctypes.c_void_p),
        ("mtimes", ctypes.c_void_p), ("sizes", ctypes.c_void_p),
        ("n_missing", ctypes.c_int64), ("missing", ctypes.c_void_p),
    ]


class _FsStat(ctypes.Structure):
    _fields_ = [
        ("n", ctypes.c_int64), ("mtimes", ctypes.c_void_p),
        ("sizes", ctypes.c_void_p), ("kinds", ctypes.c_void_p),
    ]


class _FsRead(ctypes.Structure):
    _fields_ = [
        ("n_read", ctypes.c_int64), ("data_len", ctypes.c_int64),
        ("data", ctypes.c_void_p), ("ends", ctypes.c_void_p),
        ("errs", ctypes.c_void_p),
    ]


lib.pw_fs_list.argtypes = [
    ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64
]
lib.pw_fs_list.restype = ctypes.POINTER(_FsList)
lib.pw_fs_list_free.argtypes = [ctypes.POINTER(_FsList)]
lib.pw_fs_list_free.restype = None
lib.pw_fs_stat.argtypes = [ctypes.c_char_p, ctypes.c_int64]
lib.pw_fs_stat.restype = ctypes.POINTER(_FsStat)
lib.pw_fs_stat_free.argtypes = [ctypes.POINTER(_FsStat)]
lib.pw_fs_stat_free.restype = None
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


def _counted(paths: bytes, n: int) -> None:
    """Native code walks ``n`` NUL-ended paths in ``paths``: they are there."""
    found = paths.count(b"\0")
    if found != n:
        raise ValueError(f"{n} paths announced, {found} NUL-ended")


def list_dir(
    root: bytes, pattern: bytes, known: bytes, n_known: int
) -> tuple[bytes, list[float], list[int], list[int], int, int]:
    """Pass 1 of a poll.  The regular files under the directory ``root``
    (ending with ``/``) whose base name matches ``pattern`` (``*`` and ``?``
    only), as ``glob.glob(root + b"**/" + pattern, recursive=True)`` finds
    them, less the ``n_known`` paths in ``known`` (each ended by NUL), which
    cost no system call: ``(the NEW paths joined by NUL in sorted order,
    st_mtime of each, st_size of each, indices of the known paths that were
    not listed, directory entries read, fstatat calls made)``.  The entries
    are -1 when ``root`` cannot be listed (it is a single file, or not
    there).  One call without the interpreter lock; see native.cpp for what
    exactly it mirrors."""
    _counted(known, n_known)
    res = lib.pw_fs_list(root, pattern, known, n_known)
    if not res:
        raise MemoryError("pw_fs_list")
    try:
        w = res.contents
        return (
            ctypes.string_at(w.paths, w.paths_len) if w.paths_len else b"",
            _list_at("f8", w.mtimes, w.n),
            _list_at("i8", w.sizes, w.n),
            _list_at("i8", w.missing, w.n_missing),
            w.entries,
            w.stats,
        )
    finally:
        lib.pw_fs_list_free(res)


#: what ``stat_files`` found at a path; ``OTHER`` is also nothing at all
REGULAR, DIRECTORY, OTHER = 0, 1, 2


def stat_files(paths: bytes, n: int) -> tuple[list[float], list[int], list[int]]:
    """Pass 2 of a poll.  One ``fstatat`` (links followed) of each of the
    ``n`` paths in ``paths`` (each ended by NUL), relative to its directory,
    which is opened once: ``(st_mtime of each, st_size of each, kind of
    each)``; time and size are 0 but for a ``REGULAR`` file.  One call
    without the interpreter lock."""
    _counted(paths, n)
    res = lib.pw_fs_stat(paths, n)
    if not res:
        raise MemoryError("pw_fs_stat")
    try:
        w = res.contents
        return (
            _list_at("f8", w.mtimes, w.n),
            _list_at("i8", w.sizes, w.n),
            _list_at("u1", w.kinds, w.n),
        )
    finally:
        lib.pw_fs_stat_free(res)


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
