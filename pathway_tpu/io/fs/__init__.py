"""``pw.io.fs`` — filesystem connector.

reference: python/pathway/io/fs/__init__.py (read:369, write) backed by the
Rust posix-like scanner (src/connectors/scanner/filesystem.rs:142,
posix_like.rs:279 — glob matching, dir polling, per-file metadata) and the
dsv/json formats (src/connectors/data_format.rs).

Here the scanner is a ``ConnectorSubject``: in streaming mode it polls the
path, diffing the (path → mtime,size) snapshot; a changed file retracts
every row it previously produced and re-emits — the upsert/delete diff
mechanism the HBM index consumes downstream (SURVEY §3.4).

One poll is two passes with a commit between them.  Pass 1 lists the path
and hands over what the listing alone shows: the names ``_seen`` does not
hold are new files (one ``fstatat`` each, read, emitted), the names of
``_seen`` that are not listed are deletions, and both are committed at once,
so the engine works on a dropped file while pass 2 runs.  Pass 2 compares
every file that was known before the poll by (mtime, size), on every poll,
and retracts, re-reads and commits the changed ones.  Listing, stats and
bytes come from the native core (``_native`` ``list_dir`` / ``stat_files`` /
``read_files``, the interpreter lock released), so pass 1 costs the entries
that are there and the files that are new, and only pass 2 one system call a
known file.  The Python lister (``glob`` + ``os.stat`` + ``open``) finds the
same files in one pass, emits them in the same order (removed, new,
changed) and takes over where :meth:`_FsSubject._native_walk_args` says it
must.

In streaming mode the two passes keep their own cadences
(:meth:`_FsSubject.run`): the listing loop on the connector's thread, the
verify rounds on a thread beside it, each with a whole ``refresh_interval``
of sleep after every pass of its own, so a new file waits for a listing and
for no check of a known file.  ``_FsSubject._emit_lock`` makes a listing
pass, and a round's compare-and-emit, one step each; a round's system calls
run outside it.
"""

from __future__ import annotations

import csv as _csv
import glob as _glob
import io as _io
import json as _json
import os
import subprocess
import sys
import threading
import time as _time
import warnings
from pathlib import Path
from typing import Any, Iterable, Iterator

from ...internals.schema import SchemaMetaclass, schema_from_types
from ...internals.table import Table
from ...internals.value import Json
from .._utils import coerce_row, input_table, with_metadata_schema
from ..streaming import ConnectorSubject, next_autogen_key
from ...internals.keys import ref_scalar

__all__ = ["read", "write"]


_NOT_LOADED = object()
#: ``pathway_tpu._native`` once a subject asked for it; ``None`` if it did
#: not build or load (warned once), and then every poll lists in Python
_native_core: Any = _NOT_LOADED
_native_core_lock = threading.Lock()


def _load_native() -> Any:
    global _native_core
    with _native_core_lock:
        if _native_core is _NOT_LOADED:
            try:
                from ... import _native as core
            except (OSError, ImportError, subprocess.CalledProcessError) as exc:
                warnings.warn(
                    f"native directory lister unavailable ({type(exc).__name__}: "
                    f"{exc}); pw.io.fs polls with its pure-Python lister: three "
                    "system calls under the interpreter lock per directory entry",
                    stacklevel=2,
                )
                core = None
            _native_core = core
        return _native_core


def _nul_ended(paths: list[str]) -> bytes:
    """``paths`` as ``_native`` takes a list of them: each ended by NUL."""
    if not paths:
        return b""
    return ("\0".join(paths) + "\0").encode("utf-8", "surrogateescape")


def _read_files_python(paths: list[str]) -> Iterator[bytes | OSError]:
    """The Python side of ``_native.read_files``."""
    for path in paths:
        try:
            with open(path, "rb") as f:
                yield f.read()
        except OSError as exc:
            yield exc


class _FsSubject(ConnectorSubject):
    """Scans ``path`` (file, dir, or glob), emitting one row per file
    (binary/plaintext) or per record (csv/json/plaintext-by-line)."""

    # every process sees the same directory: multi-process runs keep only
    # each process's owned shard of keys (io/streaming.py ownership filter)
    _shared_source = True

    def __init__(
        self,
        path: str | Path,
        fmt: str,
        schema: SchemaMetaclass,
        mode: str,
        with_metadata: bool,
        object_pattern: str,
        refresh_s: float,
        autocommit_ms: int | None,
        csv_settings=None,
        append_only: bool = False,
    ):
        super().__init__(datasource_name=f"fs:{path}")
        self.path = os.fspath(path)
        self.fmt = fmt
        self.schema_for_rows = schema
        self._mode = "static" if mode == "static" else "streaming"
        self.with_metadata = with_metadata
        self.object_pattern = object_pattern
        self.refresh_s = refresh_s
        self._autocommit_ms = autocommit_ms
        self.csv_settings = csv_settings
        #: opt-in log-tailing mode: grown files emit only new lines
        self.append_only = append_only
        self._consumed: dict[str, int] = {}
        self._overlaps: dict[str, bytes] = {}
        self._line_counts: dict[str, int] = {}
        # path -> (mtime, size, [row keys])
        self._seen: dict[str, tuple[float, int, list]] = {}
        #: held over every step that reads or writes ``_seen`` and pushes
        #: rows: a listing pass, a verify round's compare and emit.  So
        #: ``_pending`` never holds half of one pass's batch when the other
        #: commits, and ``_offsets_at_commit`` covers the committed batches
        self._emit_lock = threading.Lock()
        self._native_args = self._native_walk_args()

    # offsets = the whole scan state: restoring it suppresses re-emission of
    # unchanged files and lets later modifications retract the exact rows the
    # pre-restart run produced (reference: OffsetAntichain FilePosition
    # offsets + seek, src/connectors/offset.rs / data_storage.rs:398)
    def current_offsets(self):
        return dict(self._seen)

    def seek(self, offsets) -> None:
        if offsets:
            self._seen = dict(offsets)

    def _native_walk_args(self) -> tuple[bytes, bytes] | None:
        """``(root, pattern)`` for ``_native.list_dir``, or ``None`` where
        only the Python lister gives ``glob``'s answer: ``path`` is itself
        a glob, or ``object_pattern`` is more than ``*``, ``?`` and plain
        characters of one base name (a separator, a bracket expression,
        ``**``, nothing), or file names do not decode as UTF-8 here.  A
        ``path`` that is a single file, or is not there, is the listing's
        own to report (``entries`` < 0)."""
        pattern = self.object_pattern
        if (
            _glob.has_magic(self.path)
            or not pattern
            or pattern == "**"
            or "[" in pattern
            or os.sep in pattern
            or sys.getfilesystemencoding() != "utf-8"
        ):
            return None
        # glob joins its results to the path less its trailing separators
        root = self.path.rstrip(os.sep) or os.sep
        try:
            return (
                os.fsencode(root if root.endswith(os.sep) else root + os.sep),
                pattern.encode("utf-8"),
            )
        except UnicodeEncodeError:
            return None

    def _list_files(self) -> list[str]:
        p = self.path
        if os.path.isfile(p):
            return [p]
        if os.path.isdir(p):
            pattern = os.path.join(p, "**", self.object_pattern)
            return sorted(
                f for f in _glob.glob(pattern, recursive=True) if os.path.isfile(f)
            )
        return sorted(f for f in _glob.glob(p) if os.path.isfile(f))

    def _list(self, known: list[str]) -> tuple[list, list, list[str], Any, int, int]:
        """Pass 1: what listing the path shows against ``known``, the paths
        of ``_seen``.  Returns ``(removed, todo, listed, core, entries,
        stats)``: the known paths that are gone, ``(path, mtime, size, old
        entry of _seen)`` of each file to read in the order it is emitted,
        the known paths left for pass 2 to compare, the native core if it
        did the listing (else ``None``), the directory entries it took and
        the stat calls made.  The native listing finds the new files and
        leaves every known one that is still listed to pass 2; the Python
        lister stats every file, so its ``todo`` holds the new files and
        then the changed ones, and nothing is left."""
        core = _load_native() if self._native_args is not None else None
        if core is not None:
            blob, mtimes, sizes, missing, entries, stats = core.list_dir(
                *self._native_args, _nul_ended(known), len(known)
            )
            if entries >= 0:  # the path is a directory
                try:
                    paths = blob.decode("utf-8").split("\0") if blob else []
                except UnicodeDecodeError:
                    # a name os.fsdecode escapes sorts elsewhere as a str
                    pass
                else:
                    gone = set(missing)
                    return (
                        [known[i] for i in missing],
                        [(p, m, s, None) for p, m, s in zip(paths, mtimes, sizes)],
                        [p for i, p in enumerate(known) if i not in gone]
                        if gone else known,
                        core, entries, stats,
                    )
        seen = self._seen
        current, new, changed = set(), [], []
        for path in self._list_files():
            try:
                st = os.stat(path)
            except OSError:
                continue
            current.add(path)
            old = seen.get(path)
            if old is None:
                new.append((path, st.st_mtime, st.st_size, None))
            elif old[0] != st.st_mtime or old[1] != st.st_size:
                changed.append((path, st.st_mtime, st.st_size, old))
        removed = [p for p in known if p not in current]
        return removed, new + changed, [], None, len(current), len(current)

    def _metadata_of(self, path: str, mtime: float, size: int) -> dict | None:
        if not self.with_metadata:
            return None
        return {
            "path": os.fspath(path),
            "size": size,
            "modified_at": int(mtime),
            "seen_at": int(_time.time()),
        }

    def _rows_of_file(
        self, path: str, data: bytes, meta: dict | None
    ) -> Iterable[tuple[Any, dict]]:
        """Yield (key_material, column dict) per record of the file whose
        bytes are ``data``."""

        def attach(d: dict) -> dict:
            if meta is not None:
                d["_metadata"] = Json(meta)
            return d

        def text(**kwargs: Any) -> _io.TextIOWrapper:
            # what open(path, **kwargs) reads: the same default encoding
            # and the same newline handling, from the same class
            return _io.TextIOWrapper(_io.BytesIO(data), **kwargs)

        if self.fmt == "binary":
            yield (path,), attach({"data": data})
        elif self.fmt in ("plaintext_by_file",):
            yield (path,), attach({"data": text(errors="replace").read()})
        elif self.fmt == "plaintext":
            for i, line in enumerate(text(errors="replace")):
                yield (path, i), attach({"data": line.rstrip("\n")})
        elif self.fmt == "csv":
            settings = self.csv_settings
            reader_kwargs = settings.reader_kwargs() if settings else {}
            comment = settings.comment_character if settings else None
            f = text(newline="")
            lines = (
                (ln for ln in f if not ln.lstrip().startswith(comment))
                if comment
                else f
            )
            for i, rec in enumerate(_csv.DictReader(lines, **reader_kwargs)):
                yield (path, i), attach(coerce_row(self.schema_for_rows, rec))
        elif self.fmt in ("json", "jsonlines"):
            for i, line in enumerate(text()):
                line = line.strip()
                if not line:
                    continue
                rec = _json.loads(line)
                yield (path, i), attach(coerce_row(self.schema_for_rows, rec))
        else:
            raise ValueError(f"unknown format {self.fmt!r}")

    def _emit_file(self, path: str, data: bytes, meta: dict | None) -> list:
        keys = []
        pk_cols = self._primary_key
        for key_material, row in self._rows_of_file(path, data, meta):
            values = tuple(row.get(n) for n in self._column_names)
            if pk_cols:
                key = ref_scalar(*[row.get(c) for c in pk_cols])
            else:
                key = ref_scalar("__fs__", *key_material)
            self._add_inner(key, values)
            keys.append((key, values))
        return keys

    def _scan_once(self) -> bool:
        return self._scan_and_emit()[0]

    def _scan_and_emit(self) -> tuple[bool, dict]:
        """One poll of the path, both passes in turn on the calling thread:
        :meth:`_listing_pass`, then, where the native core listed and a
        known file is still there, :meth:`_verify_pass` over the files that
        were known before the poll.  Returns ``(anything changed, the scan
        span's attrs)``."""
        changed, attrs, core, listed = self._listing_pass()
        if listed:
            found, entered = self._verify_pass(core, listed)
            changed = changed or found
            if entered:
                # a known name is a directory now: pass 1 took it for the file
                # it was, so what `**` finds in it is listed by a poll without it
                self._scan_and_emit()
        return changed, attrs

    def _listing_pass(self) -> tuple[bool, dict, Any, list[str]]:
        """Pass 1 (the span ``connector.scan``): list the path, emit and
        commit the new and the removed files, all under the emit lock.
        Returns ``(anything changed, the span's attrs, the native core if
        it listed, the known paths left for pass 2 to compare)``."""
        from ...internals.flight_recorder import span
        from ...internals.monitoring import (
            record_connector_files,
            record_connector_scan,
        )

        label = self._metrics_label or f"{self._datasource_name}-0"
        clock = _time.perf_counter
        with span("connector.scan", "connector", record=False) as scan, \
                self._emit_lock:
            t_start = clock()
            removed, todo, listed, core, entries, stats = self._list(list(self._seen))
            t_listed = clock()
            files = self._emit(removed, todo, core)
            changed = bool(removed or files)
            scan.set(
                files=files, removed=len(removed), native=core is not None,
                entries=entries, stats=stats,
                walk_ms=round((t_listed - t_start) * 1e3, 3),
                emit_ms=round((clock() - t_listed) * 1e3, 3),
            )
            if changed:
                # an empty poll is no part of a document's way: it shows
                # in a profiler session only, not in the ring or the stage
                scan.record = True
                scan.stage = "connector.scan"
        record_connector_scan(label, "native" if core is not None else "python")
        record_connector_files(label, "listing", files)
        return changed, scan.attrs, core, listed

    def _verify_pass(
        self, core: Any, listed: list[str] | None = None
    ) -> tuple[bool, bool]:
        """Pass 2 (the span ``connector.verify``): one ``fstatat`` of every
        path in ``listed`` (of every known file where it is ``None``),
        outside the emit lock; then, under it, the comparison with the
        (mtime, size) ``_seen`` holds, and the retraction, re-read and commit
        of what changed, in path order, and of what is no regular file any
        more (a link whose target went, a file unlinked since the listing).
        An entry of ``_seen`` that a listing pass has taken or replaced
        since the stats were asked for is left alone: that pass knows
        better.  Returns ``(anything changed, one of the removed is a
        directory now, which `**` enters)``."""
        from ...internals.flight_recorder import span
        from ...internals.monitoring import record_connector_files

        label = self._metrics_label or f"{self._datasource_name}-0"
        clock = _time.perf_counter
        seen = self._seen
        # staged on every pass, so that its mean is known where nothing is
        # ever modified; in the ring when it found something
        with span("connector.verify", "connector", stage="connector.verify",
                  record=False) as verify:
            t_start = clock()
            with self._emit_lock:
                snapshot = (list(seen.items()) if listed is None
                            else [(path, seen[path]) for path in listed])
            mtimes, sizes, kinds = core.stat_files(
                _nul_ended([path for path, _ in snapshot]), len(snapshot)
            )
            walk_ms = round((clock() - t_start) * 1e3, 3)
            removed, todo, entered = [], [], False
            with self._emit_lock:
                for (path, old), mtime, size, kind in zip(
                        snapshot, mtimes, sizes, kinds):
                    if seen.get(path) is not old:
                        continue
                    if kind != core.REGULAR:
                        removed.append(path)
                        entered = entered or kind == core.DIRECTORY
                    elif old[0] != mtime or old[1] != size:
                        todo.append((path, mtime, size, old))
                todo.sort()  # paths differ, so nothing else is compared
                files = self._emit(removed, todo, core)
            verify.set(known=len(snapshot), changed=files,
                       removed=len(removed), walk_ms=walk_ms)
            verify.record = bool(removed or files)
        record_connector_files(label, "verify", files)
        return verify.record, entered

    def _emit(self, removed: list[str], todo: list[tuple], core: Any) -> int:
        """Retract the rows of ``removed``, read and emit ``todo`` (a changed
        file's old rows retracted first), and commit if that was anything.
        Returns the files emitted."""
        seen = self._seen
        for path in removed:
            _, _, keys = seen.pop(path)
            self._append_state_clear(path)
            for key, values in keys:
                self._remove(key, values)
        emitted = 0
        if self.append_only and self.fmt in ("plaintext", "json", "jsonlines"):
            for path, mtime, size, old in todo:
                emitted += self._scan_append_mode(path, old, mtime, size)
        elif todo:
            names = [t[0] for t in todo]
            if core is not None:
                contents = core.read_files([os.fsencode(n) for n in names])
            else:
                contents = _read_files_python(names)
            for (path, mtime, size, old), data in zip(todo, contents):
                if isinstance(data, OSError):
                    continue  # gone or unreadable since the listing: next poll
                if old is not None:
                    for key, values in old[2]:
                        self._remove(key, values)
                keys = self._emit_file(
                    path, data, self._metadata_of(path, mtime, size)
                )
                seen[path] = (mtime, size, keys)
                emitted += 1
        if removed or emitted:
            self.commit()
        return emitted

    # ---- append-only tailing (opt-in log mode) --------------------------

    #: bytes of pre-growth tail re-read to confirm a pure append
    _APPEND_OVERLAP = 64

    def _append_state_clear(self, path: str) -> None:
        self._consumed.pop(path, None)
        self._overlaps.pop(path, None)
        self._line_counts.pop(path, None)

    def _emit_record(self, path, line_idx, row, keys, meta) -> None:
        """One row into the stream — the single emit contract shared by
        the append reader (the full-read path keeps _emit_file)."""
        if meta is not None:
            row["_metadata"] = Json(meta)
        values = tuple(row.get(n) for n in self._column_names)
        if self._primary_key:
            key = ref_scalar(*[row.get(c) for c in self._primary_key])
        else:
            key = ref_scalar("__fs__", path, line_idx)
        self._add_inner(key, values)
        keys.append((key, values))

    def _scan_append_mode(self, path, old, mtime, size) -> bool:
        """Grown files consume only their new complete lines; anything
        else (first sight, shrink/rotation, overlap mismatch, state lost
        in a persistence restore) retracts and re-reads from offset 0
        through the same byte reader, so both paths emit identical
        values (CRLF handling included)."""
        grown = (
            old is not None
            and size >= old[1]
            and path in self._consumed  # restore drops append state
        )
        if grown:
            keys = old[2]
            try:
                if self._read_line_region(path, keys, mtime, size):
                    self._seen[path] = (mtime, size, keys)
                    return True
            except OSError:
                return False
        # full reset + re-read
        if old is not None:
            for key, values in old[2]:
                self._remove(key, values)
        self._append_state_clear(path)
        keys: list = []
        try:
            self._read_line_region(path, keys, mtime, size)
        except OSError:
            return old is not None
        self._seen[path] = (mtime, size, keys)
        return True

    def _read_line_region(
        self, path: str, keys: list, mtime: float, size: int
    ) -> bool:
        """Consume complete lines from ``_consumed[path]`` (0 when fresh),
        emitting rows keyed by file line index; updates consumed offset,
        line count, and the overlap snapshot.  Returns False when the
        pre-growth overlap no longer matches (not a pure append).

        The ``tail -F`` trade-off applies: an in-place edit strictly
        before the overlap window is only caught by the default mode.
        Partial trailing lines are held until their newline arrives
        (writers may flush mid-line)."""
        consumed = self._consumed.get(path, 0)
        line_idx = self._line_counts.get(path, 0)
        with open(path, "rb") as f:
            lap = min(self._APPEND_OVERLAP, consumed)
            overlap = b""
            if lap:
                f.seek(consumed - lap)
                overlap = f.read(lap)
                stored = self._overlaps.get(path)
                if stored is not None and overlap != stored[-lap:]:
                    return False
            new_data = f.read()
        cut = new_data.rfind(b"\n")
        if cut < 0:
            return True  # grew, but no complete new line yet
        block = new_data[: cut + 1]
        meta = self._metadata_of(path, mtime, size)
        for line in block.decode("utf-8", errors="replace").split("\n")[:-1]:
            if line.endswith("\r"):
                # text-mode universal newlines give the full-read path
                # \r\n -> \n; match it byte-side
                line = line[:-1]
            if self.fmt in ("json", "jsonlines"):
                if line.strip():
                    self._emit_record(
                        path, line_idx,
                        coerce_row(self.schema_for_rows, _json.loads(line)),
                        keys, meta,
                    )
            else:  # plaintext
                self._emit_record(path, line_idx, {"data": line}, keys, meta)
            line_idx += 1
        self._consumed[path] = consumed + cut + 1
        self._line_counts[path] = line_idx
        self._overlaps[path] = (overlap + block)[-self._APPEND_OVERLAP:]
        return True

    def run(self) -> None:
        """A synchronous poll, then (streaming) the listing loop: a whole
        ``refresh_s`` of sleep, a listing pass, again.  Once a listing pass
        leaves known files to compare, the verify rounds run on a thread
        beside it (``<this thread's name>-verify``): a round, a whole
        ``refresh_s`` of sleep, again.  That thread is this call's: it ends
        before the call returns or raises, and a fault of a round is raised
        here, where the supervisor sees it and starts both again."""
        from ...internals.flight_recorder import observe_stage

        began = _time.monotonic()
        self._scan_once()
        if self._mode == "static":
            return
        # of this call alone: `relist` wakes the listing loop before its sleep
        # is over (a round asks for a pass, or failed), `stop` ends the rounds
        relist, stop, faults, verifier = threading.Event(), threading.Event(), [], None
        try:
            while not self._closed.is_set():
                self._pause(relist)
                relist.clear()
                if faults:
                    raise faults[0]
                # the period a new file waits in: one listing pass's start
                # to the next one's
                now = _time.monotonic()
                observe_stage("connector.period", (now - began) * 1e3)
                began = now
                _changed, _attrs, core, listed = self._listing_pass()
                if listed and verifier is None:
                    verifier = threading.Thread(
                        target=self._verify_rounds,
                        args=(core, relist, stop, faults),
                        name=f"{threading.current_thread().name}-verify",
                        daemon=True,
                    )
                    verifier.start()
        finally:
            stop.set()
            if verifier is not None:
                verifier.join()

    def _verify_rounds(
        self, core: Any, relist: threading.Event, stop: threading.Event,
        faults: list,
    ) -> None:
        """The verify thread's body, until :meth:`run` sets ``stop``."""
        from ...internals.flight_recorder import name_thread

        name_thread(threading.current_thread().name)
        try:
            while not stop.is_set():
                _, entered = self._verify_pass(core)
                if entered:
                    # a known name is a directory now: what `**` finds in it
                    # is listed by a pass without it, which need not wait
                    relist.set()
                self._pause(stop)
        except BaseException as exc:  # noqa: BLE001 - raised again by run()
            faults.append(exc)
            relist.set()

    def _pause(self, wake: threading.Event) -> None:
        """A pass's sleep: ``refresh_s``, whole, unless ``wake`` is set."""
        from ...internals.flight_recorder import span

        # profiler only: "between two passes" is an answer an idle gap
        # can get, and no news for the ring
        with span("connector.sleep", "connector", record=False):
            wake.wait(self.refresh_s)


def read(
    path: str | Path,
    *,
    format: str = "csv",
    schema: SchemaMetaclass | None = None,
    mode: str = "streaming",
    with_metadata: bool = False,
    object_pattern: str = "*",
    autocommit_duration_ms: int | None = 1500,
    refresh_interval: float = 1.0,
    persistent_id: str | None = None,
    csv_settings=None,
    append_only: bool = False,
    **kwargs: Any,
) -> Table:
    """Read files under ``path`` (reference io/fs/__init__.py:369).

    format: "csv" | "json" (jsonlines) | "plaintext" (row per line) |
    "plaintext_by_file" | "binary".  mode: "streaming" polls for
    new/changed/deleted files; "static" reads once at build time.

    A poll of a directory is two passes in native code with the interpreter
    lock released, and in streaming mode each keeps its own cadence.  The
    listing pass lists the directory, reads the files it did not know and
    commits them with the deletions: new and removed files are found by the
    listing pass, which begins ``refresh_interval`` after the last one
    ended, so a new file waits for no check of another file.  The verify
    round compares every known file by (mtime, size) and commits the changed
    ones, a changed file's retraction and its new rows in one commit:
    changed files are found by the verify round, which begins
    ``refresh_interval`` after the last one ended, on a thread beside the
    listing loop, never later than when the two took turns.  Each pass is
    its own commit.  The first poll of a run, and a static read, make both
    passes in turn.  The Python lister (``glob``) does the poll in one pass,
    one commit and one thread when the native core did not load, when
    ``path`` is a single file or a glob, or when ``object_pattern`` holds a
    path separator or a bracket expression; rows, keys and offsets are the
    same, ``pathway_connector_scans_total{lister=}`` says which it was,
    ``pathway_connector_files_total{found=}`` which pass found a file, and
    ``pathway_request_stage_ms{stage="connector.period"}`` how far apart two
    listing passes began.

    ``append_only=True`` (plaintext/jsonlines): grown files emit only
    their new complete lines instead of retract + full re-read — linear
    instead of quadratic on log-style appends.  Non-append modifications
    are detected via a tail-overlap check (``tail -F`` semantics: an
    in-place edit strictly before the overlap window needs the default
    mode) and fall back to the full re-read.
    """
    if append_only and format not in ("plaintext", "json", "jsonlines"):
        raise ValueError(
            "append_only=True supports line formats (plaintext/jsonlines), "
            f"not {format!r}"
        )
    if format in ("binary",):
        schema = schema_from_types(data=bytes)
    elif format in ("plaintext", "plaintext_by_file"):
        schema = schema_from_types(data=str)
    elif schema is None:
        raise ValueError(f"format {format!r} requires a schema")
    row_schema = schema
    out_schema = with_metadata_schema(schema) if with_metadata else schema
    subject = _FsSubject(
        path,
        format,
        row_schema,
        mode,
        with_metadata,
        object_pattern,
        refresh_interval,
        autocommit_duration_ms,
        csv_settings=csv_settings,
        append_only=append_only,
    )
    subject.persistent_id = persistent_id
    subject._configure(out_schema, schema.primary_key_columns())
    return input_table(out_schema, subject=subject)


def write(table: Table, filename: str | Path, *, format: str = "csv") -> None:
    """Write the table's update stream to a file (reference FileWriter,
    src/connectors/data_storage.rs:649 + dsv/json formatters)."""
    if format == "csv":
        from .. import csv as _csv_mod

        _csv_mod.write(table, filename)
    elif format in ("json", "jsonlines"):
        from .. import jsonlines as _jl

        _jl.write(table, filename)
    else:
        raise ValueError(f"unknown format {format!r}")
