"""``pw.io.fs`` — filesystem connector.

reference: python/pathway/io/fs/__init__.py (read:369, write) backed by the
Rust posix-like scanner (src/connectors/scanner/filesystem.rs:142,
posix_like.rs:279 — glob matching, dir polling, per-file metadata) and the
dsv/json formats (src/connectors/data_format.rs).

Here the scanner is a ``ConnectorSubject``: in streaming mode it polls the
path, diffing the (path → mtime,size) snapshot; a changed file retracts
every row it previously produced and re-emits — the upsert/delete diff
mechanism the HBM index consumes downstream (SURVEY §3.4).
"""

from __future__ import annotations

import csv as _csv
import glob as _glob
import json as _json
import os
import time as _time
from pathlib import Path
from typing import Any, Iterable

from ...internals.schema import SchemaMetaclass, schema_from_types
from ...internals.table import Table
from ...internals.value import Json
from .._utils import coerce_row, input_table, with_metadata_schema
from ..streaming import ConnectorSubject, next_autogen_key
from ...internals.keys import ref_scalar

__all__ = ["read", "write"]


def _file_metadata(path: str) -> dict:
    st = os.stat(path)
    return {
        "path": os.fspath(path),
        "size": st.st_size,
        "modified_at": int(st.st_mtime),
        "seen_at": int(_time.time()),
    }


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

    # offsets = the whole scan state: restoring it suppresses re-emission of
    # unchanged files and lets later modifications retract the exact rows the
    # pre-restart run produced (reference: OffsetAntichain FilePosition
    # offsets + seek, src/connectors/offset.rs / data_storage.rs:398)
    def current_offsets(self):
        return dict(self._seen)

    def seek(self, offsets) -> None:
        if offsets:
            self._seen = dict(offsets)

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

    def _rows_of_file(self, path: str) -> Iterable[tuple[Any, dict]]:
        """Yield (key_material, column dict) per record."""
        meta = _file_metadata(path) if self.with_metadata else None

        def attach(d: dict) -> dict:
            if meta is not None:
                d["_metadata"] = Json(meta)
            return d

        if self.fmt == "binary":
            with open(path, "rb") as f:
                yield (path,), attach({"data": f.read()})
        elif self.fmt in ("plaintext_by_file",):
            with open(path, "r", errors="replace") as f:
                yield (path,), attach({"data": f.read()})
        elif self.fmt == "plaintext":
            with open(path, "r", errors="replace") as f:
                for i, line in enumerate(f):
                    yield (path, i), attach({"data": line.rstrip("\n")})
        elif self.fmt == "csv":
            settings = self.csv_settings
            reader_kwargs = settings.reader_kwargs() if settings else {}
            comment = settings.comment_character if settings else None
            with open(path, newline="") as f:
                lines = (
                    (ln for ln in f if not ln.lstrip().startswith(comment))
                    if comment
                    else f
                )
                for i, rec in enumerate(_csv.DictReader(lines, **reader_kwargs)):
                    yield (path, i), attach(coerce_row(self.schema_for_rows, rec))
        elif self.fmt in ("json", "jsonlines"):
            with open(path) as f:
                for i, line in enumerate(f):
                    line = line.strip()
                    if not line:
                        continue
                    rec = _json.loads(line)
                    yield (path, i), attach(coerce_row(self.schema_for_rows, rec))
        else:
            raise ValueError(f"unknown format {self.fmt!r}")

    def _emit_file(self, path: str) -> list:
        keys = []
        pk_cols = self._primary_key
        for key_material, row in self._rows_of_file(path):
            values = tuple(row.get(n) for n in self._column_names)
            if pk_cols:
                key = ref_scalar(*[row.get(c) for c in pk_cols])
            else:
                key = ref_scalar("__fs__", *key_material)
            self._add_inner(key, values)
            keys.append((key, values))
        return keys

    def _scan_once(self) -> bool:
        from ...internals.flight_recorder import span

        with span("connector.scan", "connector", record=False) as timed:
            changed, emitted = self._scan_and_emit()
            timed.set(files=emitted)
            if changed:
                # an empty poll is no part of a document's way: it shows
                # in a profiler session only, not in the ring or the stage
                timed.record = True
                timed.stage = "connector.scan"
        return changed

    def _scan_and_emit(self) -> tuple[bool, int]:
        """One pass over the path: ``(anything changed, files emitted)``."""
        changed = False
        emitted = 0
        current = {}
        for path in self._list_files():
            try:
                st = os.stat(path)
            except OSError:
                continue
            current[path] = (st.st_mtime, st.st_size)
        # deletions
        for path in list(self._seen):
            if path not in current:
                _, _, keys = self._seen.pop(path)
                self._append_state_clear(path)
                for key, values in keys:
                    self._remove(key, values)
                changed = True
        # additions / modifications
        for path, (mtime, size) in current.items():
            old = self._seen.get(path)
            if old is not None and (old[0], old[1]) == (mtime, size):
                continue
            if self.append_only and self.fmt in (
                "plaintext", "json", "jsonlines"
            ):
                if self._scan_append_mode(path, old, mtime, size):
                    changed = True
                    emitted += 1
                continue
            if old is not None:
                for key, values in old[2]:
                    self._remove(key, values)
            try:
                keys = self._emit_file(path)
            except OSError:
                continue
            self._seen[path] = (mtime, size, keys)
            changed = True
            emitted += 1
        if changed:
            self.commit()
        return changed, emitted

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
                if self._read_line_region(path, keys):
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
            self._read_line_region(path, keys)
        except OSError:
            return old is not None
        self._seen[path] = (mtime, size, keys)
        return True

    def _read_line_region(self, path: str, keys: list) -> bool:
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
        meta = _file_metadata(path) if self.with_metadata else None
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
        self._scan_once()
        if self._mode == "static":
            return
        from ...internals.flight_recorder import span

        while not self._closed.is_set():
            # profiler only: "between two polls" is an answer an idle gap
            # can get, and no news for the ring
            with span("connector.sleep", "connector", record=False):
                _time.sleep(self.refresh_s)
            self._scan_once()


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
