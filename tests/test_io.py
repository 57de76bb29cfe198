"""io connector tests: fs static/streaming, python subjects, csv/jsonlines
write, subscribe, REST connector end-to-end over real HTTP."""

import json
import threading
import time
import urllib.request

import pytest

import pathway_tpu as pw


def test_fs_read_csv_static(tmp_path):
    (tmp_path / "a.csv").write_text("name,age\nalice,30\nbob,25\n")

    class S(pw.Schema):
        name: str
        age: int

    t = pw.io.fs.read(tmp_path, format="csv", schema=S, mode="static")
    df = pw.debug.table_to_pandas(t)
    assert sorted(zip(df["name"], df["age"])) == [("alice", 30), ("bob", 25)]


def test_fs_read_plaintext_and_binary_static(tmp_path):
    (tmp_path / "x.txt").write_text("hello\nworld\n")
    t = pw.io.plaintext.read(tmp_path, mode="static")
    df = pw.debug.table_to_pandas(t)
    assert sorted(df["data"]) == ["hello", "world"]

    pw.global_graph.clear()
    t2 = pw.io.fs.read(tmp_path, format="binary", mode="static", with_metadata=True)
    df2 = pw.debug.table_to_pandas(t2)
    assert df2["data"].tolist() == [b"hello\nworld\n"]
    meta = df2["_metadata"].tolist()[0]
    assert meta["path"].value.endswith("x.txt")


def test_python_connector_streaming_subscribe():
    class Numbers(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(5):
                self.next(value=i)
                if i % 2 == 1:
                    self.commit()

    class S(pw.Schema):
        value: int

    t = pw.io.python.read(Numbers(), schema=S)
    total = t.reduce(s=pw.reducers.sum(t.value))
    seen = []
    pw.io.subscribe(
        total, on_change=lambda key, row, time, add: seen.append((row["s"], add))
    )
    pw.run()
    # final state: sum = 0+1+2+3+4 = 10
    adds = [s for s, add in seen if add]
    assert adds[-1] == 10


def test_fs_streaming_upsert_delete(tmp_path):
    """Changed files retract old rows; deleted files retract everything."""
    (tmp_path / "d.txt").write_text("v1")
    t = pw.io.fs.read(
        tmp_path, format="plaintext_by_file", mode="streaming", refresh_interval=0.05
    )
    states = []
    pw.io.subscribe(
        t, on_change=lambda key, row, time, add: states.append((row["data"], add))
    )
    subject = t._operator.params["subject"]

    def mutate():
        time.sleep(0.4)
        f = tmp_path / "d.txt"
        f.write_text("v2-longer")  # size change forces re-read
        time.sleep(0.4)
        f.unlink()
        time.sleep(0.4)
        subject.close()

    th = threading.Thread(target=mutate)
    th.start()
    pw.run()
    th.join()
    assert ("v1", True) in states
    assert ("v1", False) in states
    assert ("v2-longer", True) in states
    assert ("v2-longer", False) in states


def test_csv_and_jsonlines_write(tmp_path):
    class S(pw.Schema):
        a: int

    rows = pw.debug.table_from_markdown(
        """
        a
        1
        2
        """
    )
    out_csv = tmp_path / "out.csv"
    out_jl = tmp_path / "out.jsonl"
    pw.io.csv.write(rows, out_csv)
    pw.io.jsonlines.write(rows, out_jl)
    pw.run()
    lines = out_csv.read_text().strip().splitlines()
    assert lines[0] == "a,time,diff"
    assert len(lines) == 3
    recs = [json.loads(l) for l in out_jl.read_text().strip().splitlines()]
    assert sorted(r["a"] for r in recs) == [1, 2]
    assert all(r["diff"] == 1 for r in recs)


def test_rest_connector_echo():
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    class QuerySchema(pw.Schema):
        query: str

    queries, response_writer = pw.io.http.rest_connector(
        host="127.0.0.1",
        port=port,
        schema=QuerySchema,
        delete_completed_queries=False,
    )
    results = queries.select(result=pw.apply_with_type(lambda q: q + "!", str, pw.this.query))
    response_writer(results)

    subject = queries._operator.params["subject"]
    th = threading.Thread(target=pw.run, daemon=True)
    th.start()
    try:
        deadline = time.time() + 10
        resp = None
        while time.time() < deadline:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/",
                    data=json.dumps({"query": "hi"}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                resp = json.loads(urllib.request.urlopen(req, timeout=5).read())
                break
            except (ConnectionError, urllib.error.URLError):
                time.sleep(0.1)
        assert resp == "hi!"
    finally:
        subject.close()
        th.join(timeout=10)


def test_csv_parser_settings(tmp_path):
    """CsvParserSettings (reference io/_utils.py:125): delimiter, quote,
    and comment-character control of the csv reader."""
    p = tmp_path / "data.csv"
    p.write_text(
        "# a comment line\n"
        "name;age\n"
        "'van der Berg; Jan';41\n"
        "bo;28\n"
    )
    settings = pw.io.CsvParserSettings(
        delimiter=";", quote="'", comment_character="#"
    )

    class S(pw.Schema):
        name: str
        age: int

    t = pw.io.csv.read(str(p), schema=S, mode="static", parser_settings=settings)
    df = pw.debug.table_to_pandas(t)
    assert sorted(df["name"]) == ["bo", "van der Berg; Jan"], df
    assert sorted(df["age"]) == [28, 41]


def test_fs_append_only_tailing(tmp_path):
    """append_only=True: grown files emit only their new complete lines
    (no retract/full re-read); non-append modifications fall back."""
    log = tmp_path / "app.log"
    log.write_text("l0\nl1\n")
    t = pw.io.fs.read(
        tmp_path, format="plaintext", mode="streaming", refresh_interval=0.05,
        append_only=True,
    )
    events = []
    pw.io.subscribe(
        t, on_change=lambda k, row, tm, add: events.append((row["data"], add))
    )
    subject = t._operator.params["subject"]

    def mutate():
        time.sleep(0.5)
        with open(log, "a") as f:
            f.write("l2\n")
            f.flush()
        time.sleep(0.5)
        with open(log, "a") as f:
            f.write("l3\npartial")  # incomplete final line held back
        time.sleep(0.5)
        with open(log, "a") as f:
            f.write("-done\n")
        time.sleep(0.5)
        # non-append rewrite: earlier content changes -> full re-read
        log.write_text("X0\nX1\nX2\nX3\npartial-done\nextra\n")
        time.sleep(0.6)
        subject.close()

    th = threading.Thread(target=mutate)
    th.start()
    pw.run()
    th.join()

    adds = [d for d, a in events if a]
    # appends arrived incrementally, with zero retractions before the
    # rewrite and the partial line held until completed
    first_retract = next(
        (i for i, (_, a) in enumerate(events) if not a), len(events)
    )
    assert adds[:5] == ["l0", "l1", "l2", "l3", "partial-done"]
    assert first_retract >= 5, events[:8]
    # the rewrite retracted the changed rows and re-emitted the new
    # content ("partial-done" kept the same key+value at the same line
    # index, so its retract+re-add cancels in consolidation)
    retracted = [d for d, a in events if not a]
    assert set(retracted) >= {"l0", "l1", "l2", "l3"}
    assert {"X0", "X1", "X2", "X3", "extra"} <= set(adds)


def test_fs_append_only_rejects_csv(tmp_path):
    class S(pw.Schema):
        a: int

    with pytest.raises(ValueError, match="append_only"):
        pw.io.fs.read(
            tmp_path, format="csv", schema=S, mode="streaming",
            append_only=True,
        )


def test_fs_append_only_jsonlines_blank_lines_and_rotation(tmp_path):
    """Blank jsonlines keep file-line-index keying across the append
    boundary (no key collisions), and copytruncate-style rotation resets
    the tail state instead of poisoning it."""
    class S(pw.Schema):
        a: int

    log = tmp_path / "ev.jsonl"
    log.write_text('{"a": 1}\n\n{"a": 2}\n')
    t = pw.io.fs.read(
        tmp_path, format="jsonlines", schema=S, mode="streaming",
        refresh_interval=0.05, append_only=True,
    )
    events = []
    pw.io.subscribe(
        t, on_change=lambda k, row, tm, add: events.append((k, row["a"], add))
    )
    subject = t._operator.params["subject"]

    def mutate():
        time.sleep(0.45)
        with open(log, "a") as f:
            f.write('{"a": 3}\n')
        time.sleep(0.45)
        log.write_text('{"a": 10}\n')  # truncate + rewrite (rotation)
        time.sleep(0.45)
        with open(log, "a") as f:
            f.write('{"a": 11}\n')  # tailing must work again post-reset
        time.sleep(0.45)
        subject.close()

    th = threading.Thread(target=mutate)
    th.start()
    pw.run()
    th.join()

    adds = [(k, v) for k, v, add in events if add]
    assert [v for _, v in adds[:3]] == [1, 2, 3]
    # distinct keys for every added row (the blank line must not make
    # the appended record collide with an existing key)
    assert len({k for k, _ in adds[:3]}) == 3
    assert [v for _, v in adds[3:]] == [10, 11]
    # rotation retracted the pre-truncation rows
    removed = [v for _, v, add in events if not add]
    assert set(removed) >= {1, 2, 3}


# ---------------------------------------------------------------------------
# pw.io.fs: the native directory pass against the Python lister.  Both are
# driven poll by poll over ONE directory, so they see the same files with
# the same times; the Python lister is forced the way a failed build leaves
# the module (the loaded core is None), not by a switch.
# ---------------------------------------------------------------------------

import builtins  # noqa: E402
import glob as glob_mod  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import warnings  # noqa: E402

from pathway_tpu.internals import flight_recorder  # noqa: E402
from pathway_tpu.internals.monitoring import (  # noqa: E402
    connector_files,
    connector_scans,
    exposition,
)
from pathway_tpu.io import fs as fs_mod  # noqa: E402

try:
    from pathway_tpu import _native as _built_core
except Exception:  # noqa: BLE001 - no compiler here: the Python lister's tests still run
    _built_core = None
needs_native = pytest.mark.skipif(_built_core is None, reason="native core did not build")

FS_FORMATS = ("binary", "plaintext_by_file", "plaintext", "csv", "json")


class _Person(pw.Schema):
    name: str
    age: int


def _body(fmt: str, tag: str) -> bytes:
    """A file of the format; equal-length tags give equal-length files.
    The text formats carry \\r\\n and a byte that is not UTF-8."""
    if fmt == "csv":
        return f"name,age\r\n{tag},30\nbo {tag},25\n".encode()
    if fmt == "json":
        return (f'{{"name": "{tag}", "age": 30}}\n\n'
                f'{{"name": "bo {tag}", "age": 25}}\r\n').encode()
    return f"h\xe9llo {tag}\r\nworld\n\nend {tag}".encode("latin-1")


def _put(path, data: bytes) -> None:
    """Write beside, rename in: the reader never sees half a file."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = os.path.join(os.path.dirname(path), ".putting")
    with builtins.open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)


def _fs_subject(path, fmt: str, **kwargs):
    schema = _Person if fmt in ("csv", "json", "jsonlines") else None
    t = pw.io.fs.read(path, format=fmt, schema=schema, mode="streaming",
                      with_metadata=True, **kwargs)
    return t._operator.params["subject"]


def _poll(subject, native: bool, monkeypatch) -> list:
    """One poll under the named lister: the (op, key, values) it committed."""
    with monkeypatch.context() as m:
        if not native:
            m.setattr(fs_mod, "_native_core", None)
        _changed, attrs = subject._scan_and_emit()
    assert attrs["native"] is native
    with subject._lock:
        batches, subject._committed = subject._committed, []
    return [event for batch in batches for event in batch]


def _case_new_file(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    yield
    _put(f"{d}/b.txt", _body(fmt, "b1"))


def _case_edit_same_size(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    _put(f"{d}/b.txt", _body(fmt, "b1"))
    yield
    st = os.stat(f"{d}/a.txt")
    with builtins.open(f"{d}/a.txt", "r+b") as f:  # in place: same inode, same size
        f.write(_body(fmt, "a2"))
    os.utime(f"{d}/a.txt", ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
    assert os.stat(f"{d}/a.txt").st_size == st.st_size


def _case_grown(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    yield
    with builtins.open(f"{d}/a.txt", "ab") as f:
        f.write(b"\n" + _body(fmt, "a2").split(b"\n", 1)[1])


def _case_deleted(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    _put(f"{d}/b.txt", _body(fmt, "b1"))
    _put(f"{d}/c.txt", _body(fmt, "c1"))
    yield
    os.unlink(f"{d}/b.txt")


def _case_replaced_by_rename(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    yield
    _put(f"{d}/a.txt", _body(fmt, "a2 longer"))


def _case_nested(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    _put(f"{d}/sub/b.txt", _body(fmt, "b1"))
    _put(f"{d}/sub/deep/c.txt", _body(fmt, "c1"))
    _put(f"{d}/a-b/d.txt", _body(fmt, "d1"))  # '-' sorts before '/'
    yield
    _put(f"{d}/sub/deep/e.txt", _body(fmt, "e1"))
    os.unlink(f"{d}/sub/b.txt")


def _case_hidden(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    _put(f"{d}/.hidden.txt", _body(fmt, "h1"))
    _put(f"{d}/.hdir/x.txt", _body(fmt, "x1"))
    yield
    _put(f"{d}/.hdir/y.txt", _body(fmt, "y1"))
    _put(f"{d}/.later.txt", _body(fmt, "l1"))
    _put(f"{d}/b.txt", _body(fmt, "b1"))


def _case_symlinks(d, fmt, outside):
    _put(f"{outside}/target.txt", _body(fmt, "t1"))
    _put(f"{outside}/tree/in.txt", _body(fmt, "i1"))
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    os.symlink(f"{outside}/target.txt", f"{d}/link.txt")
    os.symlink(f"{outside}/tree", f"{d}/linkdir")
    os.symlink(f"{outside}/nothing", f"{d}/broken.txt")
    yield
    _put(f"{outside}/target.txt", _body(fmt, "t2 longer"))
    _put(f"{outside}/tree/more.txt", _body(fmt, "m1"))


def _case_link_becomes_dir(d, fmt, outside):
    _put(f"{outside}/target.txt", _body(fmt, "t1"))
    _put(f"{outside}/tree/in.txt", _body(fmt, "i1"))
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    os.symlink(f"{outside}/target.txt", f"{d}/link")
    yield
    # the known name is listed as a link, as before: only the second pass's
    # stat says that `**` enters it now, and the poll lists again
    os.unlink(f"{d}/link")
    os.symlink(f"{outside}/tree", f"{d}/link")


def _case_new_changed_removed(d, fmt, outside):
    for name in ("a", "b", "c", "d"):
        _put(f"{d}/{name}.txt", _body(fmt, f"{name}1"))
    yield
    _put(f"{d}/d.txt", _body(fmt, "d2 longer"))
    _put(f"{d}/b.txt", _body(fmt, "b2 longer"))
    os.unlink(f"{d}/c.txt")
    _put(f"{d}/sub/e.txt", _body(fmt, "e1"))
    _put(f"{d}/0.txt", _body(fmt, "01"))


def _case_pattern_txt(d, fmt, outside):
    _put(f"{d}/a.txt", _body(fmt, "a1"))
    _put(f"{d}/b.dat", _body(fmt, "b1"))
    _put(f"{d}/sub/c.txt", _body(fmt, "c1"))
    _put(f"{d}/txt", _body(fmt, "n1"))
    yield
    _put(f"{d}/d.dat", _body(fmt, "d1"))
    _put(f"{d}/e.txt", _body(fmt, "e1"))
    _put(f"{d}/sub/.f.txt", _body(fmt, "f1"))


FS_CASES = {
    "new_file": (_case_new_file, "*"),
    "edit_same_size": (_case_edit_same_size, "*"),
    "grown": (_case_grown, "*"),
    "deleted": (_case_deleted, "*"),
    "replaced_by_rename": (_case_replaced_by_rename, "*"),
    "nested": (_case_nested, "*"),
    "hidden": (_case_hidden, "*"),
    "symlinks": (_case_symlinks, "*"),
    "pattern_txt": (_case_pattern_txt, "*.txt"),
    "link_becomes_dir": (_case_link_becomes_dir, "*"),
    "new_changed_removed": (_case_new_changed_removed, "*"),
}


def _both_listers(tmp_path, monkeypatch, fmt, steps, **kwargs):
    """Drive one subject per lister over the same directory through
    ``steps`` (a generator that changes the directory between yields);
    returns the two event streams, poll by poll, and the two subjects."""
    d = tmp_path / "watched"
    d.mkdir()
    monkeypatch.setattr(fs_mod._time, "time", lambda: 1_700_000_000.5)  # seen_at
    native = _fs_subject(d, fmt, **kwargs)
    python = _fs_subject(d, fmt, **kwargs)
    assert native._native_args is not None
    streams = ([], [])
    for _ in steps(str(d)):
        streams[0].append(_poll(native, True, monkeypatch))
        streams[1].append(_poll(python, False, monkeypatch))
    for subject, stream, is_native in ((native, streams[0], True),
                                      (python, streams[1], False)):
        stream.append(_poll(subject, is_native, monkeypatch))  # after the last step
        stream.append(_poll(subject, is_native, monkeypatch))  # and nothing since
    return streams, native, python


@needs_native
@pytest.mark.parametrize("case", FS_CASES)
@pytest.mark.parametrize("fmt", FS_FORMATS)
def test_fs_native_lister_matches_python_lister(tmp_path, monkeypatch, fmt, case):
    change, pattern = FS_CASES[case]
    outside = tmp_path / "outside"

    (got, want), native, python = _both_listers(
        tmp_path, monkeypatch, fmt,
        lambda d: change(d, fmt, str(outside)), object_pattern=pattern)
    assert got == want  # same (op, key, values), same order, poll by poll
    assert got[0] and got[-2], "the case made no event"
    assert got[-1] == []  # an unchanged directory emits nothing
    assert native._seen == python._seen
    assert list(native._seen) == list(python._seen)
    assert native.current_offsets() == python.current_offsets()
    # the first poll after a change saw it: nothing is left for a later one
    for path, (mtime, size, _keys) in native._seen.items():
        st = os.stat(path)
        assert (mtime, size) == (st.st_mtime, st.st_size)


@needs_native
@pytest.mark.parametrize("fmt", ["plaintext", "jsonlines"])
def test_fs_native_lister_matches_python_lister_append_only(
        tmp_path, monkeypatch, fmt):
    line = (lambda i: f"line {i}") if fmt == "plaintext" else (
        lambda i: json.dumps({"name": f"n{i}", "age": i}))

    def steps(d):
        log = f"{d}/app.log"
        with builtins.open(log, "w") as f:
            f.write(line(0) + "\n" + line(1) + "\n")
        yield
        with builtins.open(log, "a") as f:
            f.write(line(2) + "\r\n" + line(3)[:4])  # a partial last line
        yield
        with builtins.open(log, "a") as f:
            f.write(line(3)[4:] + "\n")
        yield
        with builtins.open(log, "w") as f:  # rotation
            f.write(line(9) + "\n")
        _put(f"{d}/sub/other.log", (line(7) + "\n").encode())
        yield

    (got, want), native, python = _both_listers(
        tmp_path, monkeypatch, fmt, steps, append_only=True)
    assert got == want
    assert [len(events) for events in got] == [2, 1, 1, 6, 0, 0]
    assert native._seen == python._seen
    assert native._consumed == python._consumed


@needs_native
def test_fs_snapshot_of_python_lister_restores_under_native(tmp_path, monkeypatch):
    """The persisted offsets are ``_seen`` itself: the floats the native walk
    hands over are the ones ``os.stat`` gave the run that persisted them."""
    d = tmp_path / "watched"
    for i in range(40):
        _put(f"{d}/sub{i % 3}/doc_{i:03d}.txt", _body("plaintext", f"t{i}"))
    before = _fs_subject(d, "plaintext")
    first = _poll(before, False, monkeypatch)
    assert len(first) == 40 * 4
    offsets = pickle.loads(pickle.dumps(before.current_offsets()))

    restored = _fs_subject(d, "plaintext")
    restored.seek(offsets)
    assert _poll(restored, True, monkeypatch) == []  # nothing is emitted again
    assert restored.current_offsets() == offsets
    # and a later edit retracts exactly the rows the earlier run produced
    edited = f"{d}/sub1/doc_004.txt"
    _put(edited, _body("plaintext", "edited"))
    events = _poll(restored, True, monkeypatch)
    old_rows = [(key, values) for op, key, values in first
                if values[1].value["path"] == edited]
    assert [(key, values) for op, key, values in events if op == "delete"] == old_rows
    assert len([e for e in events if e[0] == "insert"]) == 4


class _RecordingCore:
    """The native core, with every call of a poll written down: its name,
    how many paths it was given, and the sizes of the batches the subject
    had committed when it was made."""

    def __init__(self, core, subject):
        self._core, self._subject, self.calls = core, subject, []

    def __getattr__(self, name):  # the kinds stat_files tells apart
        return getattr(self._core, name)

    def _note(self, name, n):
        with self._subject._lock:
            self.calls.append((name, n, [len(b) for b in self._subject._committed]))

    def list_dir(self, root, pattern, known, n_known):
        self._note("list_dir", n_known)
        return self._core.list_dir(root, pattern, known, n_known)

    def stat_files(self, paths, n):
        self._note("stat_files", n)
        return self._core.stat_files(paths, n)

    def read_files(self, paths):
        self._note("read_files", len(paths))
        return self._core.read_files(paths)


def _spans(name):
    return [s for s in flight_recorder.get_recorder().spans(category="connector")
            if s.name == name]


def _staged(stage):
    """Observations of ``pathway_request_stage_ms{stage=}`` so far."""
    head = f'pathway_request_stage_ms_count{{stage="{stage}"}} '
    return sum(int(float(line[len(head):].split()[0]))
               for line in flight_recorder.observability_metrics_lines()
               if line.startswith(head))


@needs_native
def test_fs_native_poll_costs_what_changed(tmp_path, monkeypatch):
    """The contract, without a clock: a poll by the native lister makes no
    Python-level file-system call per file that is there and one ``fstatat``
    a file (a first poll in its listing, a later one in its second pass);
    the new files are committed before the known ones are checked; and the
    spans say so."""
    d = tmp_path / "watched"
    for i in range(2000):
        _put(f"{d}/passage_{i:07d}.txt", b"w%d " % i * 8)
    subject = _fs_subject(d, "binary")
    label = f"{subject._datasource_name}-0"
    core = _RecordingCore(_built_core, subject)
    monkeypatch.setattr(fs_mod, "_native_core", core)
    files_before = connector_files()

    # a first poll: every file is new, so the listing stats each once and
    # no second pass runs
    flight_recorder.reset_recorder()
    verified = _staged("connector.verify")
    assert subject._scan_once() is True
    with subject._lock:
        (batch,), subject._committed = subject._committed, []
    assert len(batch) == 2000
    assert [(name, n) for name, n, _ in core.calls] == [
        ("list_dir", 0), ("read_files", 2000)]
    (scan,) = _spans("connector.scan")
    assert (scan.attrs["entries"], scan.attrs["stats"], scan.attrs["files"]) == (
        2000, 2000, 2000)
    assert _spans("connector.verify") == []
    assert _staged("connector.verify") == verified

    calls = {"os.stat": 0, "os.path.isfile": 0, "glob.glob": 0, "open": 0}

    def counting(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(os, "stat", counting("os.stat", os.stat))
    monkeypatch.setattr(os.path, "isfile", counting("os.path.isfile", os.path.isfile))
    monkeypatch.setattr(glob_mod, "glob", counting("glob.glob", glob_mod.glob))
    monkeypatch.setattr(builtins, "open", counting("open", builtins.open))

    # nothing new: one listing without a stat, one second pass over all
    # 2,000; the second pass is staged and in no ring, the first neither
    flight_recorder.reset_recorder()
    del core.calls[:]
    scanned = _staged("connector.scan")
    changed, attrs = subject._scan_and_emit()
    assert changed is False and subject._committed == []
    assert calls == {"os.stat": 0, "os.path.isfile": 0, "glob.glob": 0, "open": 0}
    assert core.calls == [("list_dir", 2000, []), ("stat_files", 2000, [])]
    assert (attrs["entries"], attrs["stats"], attrs["files"]) == (2000, 0, 0)
    assert _spans("connector.scan") == [] and _spans("connector.verify") == []
    assert _staged("connector.scan") == scanned
    assert _staged("connector.verify") == verified + 1

    for i in range(2000, 2007):
        _put(f"{d}/passage_{i:07d}.txt", b"w%d " % i * 8)
    calls.update(dict.fromkeys(calls, 0))  # _put opened seven files itself
    flight_recorder.reset_recorder()
    del core.calls[:]
    assert subject._scan_once() is True
    assert calls == {"os.stat": 0, "os.path.isfile": 0, "glob.glob": 0, "open": 0}
    # the seven are committed when the second pass starts on the 2,000 files
    # that were known before the poll
    assert core.calls == [("list_dir", 2000, []), ("read_files", 7, []),
                          ("stat_files", 2000, [7])]
    with subject._lock:
        (batch,), subject._committed = subject._committed, []
    assert len(batch) == 7
    (scan,) = _spans("connector.scan")
    assert scan.attrs["native"] is True
    assert scan.attrs["files"] == 7 and scan.attrs["removed"] == 0
    assert scan.attrs["entries"] == 2007
    assert scan.attrs["stats"] == 7  # one fstatat a NEW file
    assert scan.attrs["walk_ms"] >= 0 and scan.attrs["emit_ms"] >= 0
    assert _spans("connector.verify") == []  # it found nothing: staged only
    assert _staged("connector.scan") == scanned + 1
    assert _staged("connector.verify") == verified + 2

    # every file so far was a new name
    counted = {k: n - files_before.get(k, 0) for k, n in connector_files().items()
               if k[0] == label}
    assert counted == {(label, "listing"): 2007, (label, "verify"): 0}

    # the Python lister on the same directory pays per file that is there
    assert _poll(subject, False, monkeypatch) == []
    assert calls["os.stat"] >= 2007 and calls["os.path.isfile"] >= 2007


def _step_new_changed_removed(d):
    _put(f"{d}/b.txt", b"beta, edited")
    os.unlink(f"{d}/c.txt")
    _put(f"{d}/sub/e.txt", b"epsilon")


def _step_rename(d):
    os.replace(f"{d}/a.txt", f"{d}/z.txt")


def _step_changed_only(d):
    _put(f"{d}/b.txt", b"beta, edited")
    _put(f"{d}/a.txt", b"alpha, edited")


def _step_new_and_removed(d):
    os.unlink(f"{d}/a.txt")
    _put(f"{d}/e.txt", b"epsilon")


#: step -> what the one poll after it finds: removed, new, changed (in the
#: order they are emitted)
FS_COMMITS = {
    "new_changed_removed": (_step_new_changed_removed, ["c.txt"], ["sub/e.txt"], ["b.txt"]),
    "rename": (_step_rename, ["a.txt"], ["z.txt"], []),
    "changed_only": (_step_changed_only, [], [], ["a.txt", "b.txt"]),
    "new_and_removed": (_step_new_and_removed, ["a.txt"], ["e.txt"], []),
}


@pytest.mark.parametrize("native", [
    pytest.param(True, marks=needs_native, id="native"),
    pytest.param(False, id="python"),
])
@pytest.mark.parametrize("step", FS_COMMITS)
def test_fs_poll_commits_new_and_removed_files_before_changed_ones(
        tmp_path, monkeypatch, step, native):
    """One poll sees every new, changed and removed file.  Under the native
    lister the removed and the new ones are one commit (so a rename is one
    commit) and the changed ones the next; the Python lister commits once,
    in the same order."""
    d = tmp_path / "watched"
    for name, data in (("a", b"alpha"), ("b", b"beta"), ("c", b"gamma")):
        _put(f"{d}/{name}.txt", data)
    if not native:
        monkeypatch.setattr(fs_mod, "_native_core", None)
    subject = _fs_subject(d, "binary")
    label = f"{subject._datasource_name}-0"
    subject._scan_once()
    subject._committed.clear()
    files_before = connector_files()
    flight_recorder.reset_recorder()

    change, removed, new, changed = FS_COMMITS[step]
    change(str(d))
    assert subject._scan_once() is True
    with subject._lock:
        batches, subject._committed = subject._committed, []
    got = [[(op, os.path.relpath(values[1].value["path"], d))
            for op, _key, values in batch] for batch in batches]
    first = [("delete", p) for p in removed] + [("insert", p) for p in new]
    second = [(op, p) for p in changed for op in ("delete", "insert")]
    assert got == ([b for b in (first, second) if b] if native else [first + second])
    # the one poll left nothing for the next
    assert sorted(subject._seen) == sorted(
        os.path.join(r, f) for r, _d, fs in os.walk(d) for f in fs)
    assert subject._scan_once() is False

    counted = {k[1]: n - files_before.get(k, 0) for k, n in connector_files().items()
               if k[0] == label}
    scans, verifies = _spans("connector.scan"), _spans("connector.verify")
    if native:
        assert counted == {"listing": len(new), "verify": len(changed)}
        # each pass is in the ring where it emitted
        assert [(s.attrs["removed"], s.attrs["files"]) for s in scans] == (
            [(len(removed), len(new))] if first else [])
        assert [(v.attrs["known"], v.attrs["changed"]) for v in verifies] == (
            [(3 - len(removed), len(changed))] if second else [])
        for found, n in counted.items():
            assert (f'pathway_connector_files_total{{connector="{label}",found="{found}"}} '
                    f"{files_before.get((label, found), 0) + n}") in exposition()
    else:
        assert counted == {"listing": len(new) + len(changed)}
        assert len(scans) == 1 and verifies == []


def test_fs_python_lister_warns_once_and_is_counted(tmp_path, monkeypatch):
    """A core that does not load: one warning for the process, every poll
    under ``lister="python"``, the same rows."""
    import sys

    d = tmp_path / "watched"
    _put(f"{d}/a.txt", b"alpha")
    monkeypatch.setattr(fs_mod, "_native_core", fs_mod._NOT_LOADED)
    monkeypatch.setitem(sys.modules, "pathway_tpu._native", None)  # import fails
    monkeypatch.delattr(pw, "_native")
    subjects = [_fs_subject(d, "binary"), _fs_subject(d, "binary")]
    label = f"{subjects[0]._datasource_name}-0"
    before = connector_scans().get((label, "python"), 0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for subject in subjects:
            for _ in range(3):
                subject._scan_once()
    told = [w for w in caught if "native directory lister unavailable" in str(w.message)]
    assert len(told) == 1
    assert fs_mod._native_core is None
    assert [len(s._seen) for s in subjects] == [1, 1]
    assert connector_scans()[(label, "python")] == before + 6
    assert (f'pathway_connector_scans_total{{connector="{label}",lister="python"}} '
            f"{before + 6}") in exposition()


@pytest.mark.parametrize("path_of,pattern", [
    (lambda d: f"{d}/a.txt", "*"),        # a single file
    (lambda d: f"{d}/*.txt", "*"),        # itself a glob
    (lambda d: d, "sub/*.txt"),           # a separator in the pattern
    (lambda d: d, "[ab].txt"),            # a bracket expression
    (lambda d: f"{d}/missing", "*"),      # nothing there (yet)
], ids=["single_file", "glob_path", "separator", "bracket", "missing"])
def test_fs_python_lister_is_taken_where_it_must(tmp_path, monkeypatch, path_of, pattern):
    d = tmp_path / "watched"
    _put(f"{d}/a.txt", b"alpha")
    _put(f"{d}/b.txt", b"beta")
    _put(f"{d}/sub/c.txt", b"gamma")
    subject = _fs_subject(path_of(str(d)), "binary", object_pattern=pattern)
    changed, attrs = subject._scan_and_emit()
    assert attrs["native"] is False
    monkeypatch.setattr(fs_mod, "_native_core", None)
    twin = _fs_subject(path_of(str(d)), "binary", object_pattern=pattern)
    twin._scan_and_emit()
    assert subject._seen == twin._seen
    assert changed is bool(subject._seen)


# ---------------------------------------------------------------------------
# run() in streaming mode: the two passes of a poll on their own cadences,
# the listing loop on the connector's thread and the verify rounds on
# ``<thread>-verify``.  The contract without a clock: the verify thread's
# ``stat_files`` waits for a permit of the test's, so the test decides which
# round runs when; every wait has a timeout of its own and none is a sleep.
# ---------------------------------------------------------------------------

from pathway_tpu.io.streaming import ConnectorSupervisor  # noqa: E402

#: seconds a wait may last before its test fails; none lasts when the code is right
WAIT = 30.0
REFRESH_S = 0.01


class _LiveCore:
    """The native core under ``run()``: every call written down as ``(who,
    name, n)``, ``who`` the pass whose thread made it.  While ``held``, a
    ``stat_files`` on the verify thread waits for a permit: after it took
    the stats (``stat_first``: what it hands back is old by then) or before
    (it hands back what is there when it is let go)."""

    def __init__(self, core, stat_first=True):
        self._core, self.stat_first = core, stat_first
        self.calls, self.cond = [], threading.Condition()
        self.held, self.permits = True, threading.Semaphore(0)
        self.fault = None

    def __getattr__(self, name):
        return getattr(self._core, name)

    def note(self, name, n):
        who = ("verify" if threading.current_thread().name.endswith("-verify")
               else "listing")
        with self.cond:
            self.calls.append((who, name, n))
            self.cond.notify_all()
        return who

    def count(self, *call):
        """Calls so far that begin with ``call``."""
        return sum(1 for c in self.calls if c[:len(call)] == call)

    def list_dir(self, root, pattern, known, n_known):
        self.note("list_dir", n_known)
        return self._core.list_dir(root, pattern, known, n_known)

    def read_files(self, paths):
        self.note("read_files", len(paths))
        return self._core.read_files(paths)

    def stat_files(self, paths, n):
        held = self.note("stat_files", n) == "verify" and self.held
        if held and self.stat_first:
            stats = self._core.stat_files(paths, n)
        if held:
            self.note("waiting", n)
            assert self.permits.acquire(timeout=WAIT), "no permit for the round"
        if self.fault is not None:
            fault, self.fault = self.fault, None
            raise fault
        if not (held and self.stat_first):
            stats = self._core.stat_files(paths, n)
        return stats

    def free(self):
        """No round waits any more."""
        self.held = False
        self.permits.release(1000)


class _Live:
    """``run()`` of a streaming subject over ``d`` on a thread of its own."""

    def __init__(self, d, monkeypatch, core, supervised=False, fmt="binary"):
        self.d, self.core = str(d), core
        self.subject = subject = _fs_subject(d, fmt, refresh_interval=REFRESH_S)
        monkeypatch.setattr(fs_mod, "_native_core", core if core._core is not None else None)
        pause = subject._pause

        def pausing(wake):
            pause(wake)
            core.note("pause", "cut" if wake.is_set() else "whole")

        subject._pause = pausing
        self.commits = []  # (offsets, batches committed) after every commit
        commit = subject.commit

        def committing():
            commit()
            with subject._lock:
                self.commits.append((subject._offsets_at_commit, len(subject._committed)))

        subject.commit = committing
        self.error = None
        self.supervisor = ConnectorSupervisor(subject, "fs-live") if supervised else None
        self.thread = threading.Thread(target=self._run, name="pw-conn-7", daemon=True)

    def _run(self):
        try:
            (self.supervisor or self.subject).run()
        except BaseException as exc:  # noqa: BLE001 - handed to the test
            self.error = exc

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def stop(self):
        self.core.free()
        self.subject.close()
        self.thread.join(WAIT)
        assert not self.thread.is_alive(), "run() did not return"
        assert [t.name for t in threading.enumerate()
                if t.name.startswith("pw-conn-7")] == []

    def wait(self, what, predicate):
        with self.core.cond:
            assert self.core.cond.wait_for(predicate, timeout=WAIT), (
                f"never {what}: {self.core.calls[-12:]}")

    def wait_round(self, k):
        """The ``k``-th round (from 1) of the verify thread waits for its
        permit: every round before it has ended."""
        self.wait(f"round {k} waiting", lambda: self.core.count("verify", "waiting") >= k)

    def batches(self):
        """What was committed so far, batch by batch: ``(op, name, data)``."""
        with self.subject._lock:
            batches = list(self.subject._committed)
        return [[(op, os.path.relpath(values[1].value["path"], self.d), values[0])
                 for op, _key, values in batch] for batch in batches]

    def events(self):
        return [event for batch in self.batches() for event in batch]

    def wait_event(self, event):
        self.wait(f"committed {event}", lambda: event in self.events())

    def on_disk(self):
        out = []
        for root, _dirs, files in os.walk(self.d):
            for name in files:
                path = os.path.join(root, name)
                with builtins.open(path, "rb") as f:
                    out.append((os.path.relpath(path, self.d), f.read()))
        return sorted(out)


def _net(events):
    """The rows left by ``events``; a row is retracted only after it was
    inserted, and is there once."""
    rows = []
    for op, name, data in events:
        if op == "insert":
            assert (name, data) not in rows, f"doubled row of {name}"
            rows.append((name, data))
        else:
            rows.remove((name, data))  # ValueError: retracted what was not there
    return sorted(rows)


def _assert_cadence(calls):
    """No sleep is shortened and none is skipped: between two passes of one
    kind lies one whole ``refresh_interval`` of sleep of that pass's own."""
    for who, head in (("listing", "list_dir"), ("verify", "stat_files")):
        mine = [(name, n) for w, name, n in calls
                if w == who and name in (head, "pause")]
        if who == "listing":
            # the first poll makes both passes in turn, then the loop sleeps
            mine = [c for c in mine if c != ("stat_files", mine[0][1])]
        passes = [i for i, (name, _n) in enumerate(mine) if name == head]
        for a, b in zip(passes, passes[1:]):
            assert mine[a + 1:b] == [("pause", "whole")], (who, mine[a:b + 1])


def _edit_in_place(path, data: bytes):
    """Other bytes of the same length in the same inode, 1 ms later."""
    st = os.stat(path)
    assert len(data) == st.st_size
    with builtins.open(path, "r+b") as f:
        f.write(data)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))


def _watched(tmp_path, names=("a", "b", "c")):
    d = tmp_path / "watched"
    for name in names:
        _put(f"{d}/{name}.txt", f"{name} one".encode())
    return d


@needs_native
def test_fs_run_lists_new_files_while_a_verify_round_is_held(tmp_path, monkeypatch):
    """(a) and (g): the listing loop does not wait for a round."""
    d = _watched(tmp_path)
    periods, verifies = _staged("connector.period"), _staged("connector.verify")
    live = _Live(d, monkeypatch, _LiveCore(_built_core))
    with live:
        live.wait_round(1)
        passes = live.core.count("listing", "list_dir")
        _put(f"{d}/d.txt", b"d one")
        live.wait_event(("insert", "d.txt", b"d one"))
        _put(f"{d}/sub/e.txt", b"e one")
        live.wait_event(("insert", "sub/e.txt", b"e one"))
        live.wait("two more listing passes",
                  lambda: live.core.count("listing", "list_dir") >= passes + 2)
        # the round still holds its snapshot of three, and is the only one
        assert [c for c in live.core.calls if c[1] in ("stat_files", "waiting")] == [
            ("verify", "stat_files", 3), ("verify", "waiting", 3)]
        live.core.permits.release()
        live.wait_round(2)
        assert [c for c in live.core.calls if c[0] == "verify" and c[1] != "pause"][2:] == [
            ("verify", "stat_files", 5), ("verify", "waiting", 5)]
    calls = live.core.calls
    assert live.error is None
    # the new files were one commit each, of a listing pass; nothing else moved
    assert live.batches()[1:] == [[("insert", "d.txt", b"d one")],
                                  [("insert", "sub/e.txt", b"e one")]]
    assert _net(live.events()) == live.on_disk()
    _assert_cadence(calls)
    # one observation a listing pass after the first, one a round
    assert _staged("connector.period") - periods == live.core.count("listing", "list_dir") - 1
    assert _staged("connector.verify") - verifies == live.core.count("verify", "stat_files")
    assert live.core.count("listing", "stat_files") == 0


@needs_native
@pytest.mark.parametrize("stat_first", [True, False], ids=["during_round", "before_stats"])
def test_fs_run_emits_a_file_edited_in_place_once(tmp_path, monkeypatch, stat_first):
    """(b): a file modified in place is found by the first round whose
    stats are taken after the edit, once, delete and insert in one commit;
    no listing pass touches it."""
    d = _watched(tmp_path)
    live = _Live(d, monkeypatch, _LiveCore(_built_core, stat_first))
    with live:
        live.wait_round(1)
        _edit_in_place(f"{d}/b.txt", b"b two")
        _put(f"{d}/d.txt", b"d one")
        live.wait_event(("insert", "d.txt", b"d one"))  # a listing pass since the edit
        assert ("insert", "b.txt", b"b two") not in live.events()
        live.core.permits.release()
        live.wait_round(2)  # round 1 is over
        edited = [("delete", "b.txt", b"b one"), ("insert", "b.txt", b"b two")]
        assert (edited in live.batches()) is (not stat_first)
        live.core.permits.release()
        live.wait_round(3)  # round 2 is over
        assert edited in live.batches()
        live.core.permits.release()
        live.wait_round(4)
    assert live.error is None
    assert live.batches()[1:] == [[("insert", "d.txt", b"d one")], edited]
    assert _net(live.events()) == live.on_disk()
    _assert_cadence(live.core.calls)


@needs_native
@pytest.mark.parametrize("stat_first", [True, False], ids=["old_stats", "new_stats"])
@pytest.mark.parametrize("back", [False, True], ids=["removed", "removed_and_back"])
def test_fs_run_round_skips_what_the_listing_took(tmp_path, monkeypatch, back, stat_first):
    """(c): a round's result is applied to the entries of ``_seen`` that its
    snapshot held and no listing pass has touched since."""
    d = _watched(tmp_path)
    live = _Live(d, monkeypatch, _LiveCore(_built_core, stat_first))
    with live:
        live.wait_round(1)  # its snapshot holds b.txt
        os.unlink(f"{d}/b.txt")
        live.wait_event(("delete", "b.txt", b"b one"))
        if back:
            _put(f"{d}/b.txt", b"b two, longer")
            live.wait_event(("insert", "b.txt", b"b two, longer"))
        for k in (2, 3):
            live.core.permits.release()
            live.wait_round(k)
    assert live.error is None  # no KeyError of the round
    assert live.batches()[1:] == [[("delete", "b.txt", b"b one")]] + (
        [[("insert", "b.txt", b"b two, longer")]] if back else [])
    assert _net(live.events()) == live.on_disk()
    seen = live.subject._seen
    assert sorted(os.path.relpath(p, d) for p in seen) == [n for n, _ in live.on_disk()]
    for path, (mtime, size, _keys) in seen.items():
        st = os.stat(path)
        assert (mtime, size) == (st.st_mtime, st.st_size)
    _assert_cadence(live.core.calls)


@needs_native
def test_fs_run_round_finds_a_name_that_became_a_directory(tmp_path, monkeypatch):
    """A known name that is a directory now is retracted by the round, and
    the round asks the listing loop for a pass without it."""
    d = _watched(tmp_path)
    outside = tmp_path / "outside"
    _put(f"{outside}/target.txt", b"t one")
    _put(f"{outside}/tree/in.txt", b"i one")
    os.symlink(f"{outside}/target.txt", f"{d}/link")
    live = _Live(d, monkeypatch, _LiveCore(_built_core, stat_first=False))
    with live:
        live.wait_round(1)
        os.unlink(f"{d}/link")
        os.symlink(f"{outside}/tree", f"{d}/link")
        live.core.permits.release()
        live.wait_event(("insert", "link/in.txt", b"i one"))
    assert live.error is None
    assert live.batches()[1:] == [[("delete", "link", b"t one")],
                                  [("insert", "link/in.txt", b"i one")]]
    woken = live.core.calls.index(("listing", "pause", "cut"))
    assert live.core.calls[woken + 1][:2] == ("listing", "list_dir")


@needs_native
def test_fs_run_offsets_of_every_commit_restore(tmp_path, monkeypatch):
    """(d): whichever pass committed, ``_offsets_at_commit`` covers the
    committed batches and nothing else: a fresh subject restored from it
    emits, on the directory as it is now, what the batches lack."""
    d = _watched(tmp_path)
    live = _Live(d, monkeypatch, _LiveCore(_built_core, stat_first=False))
    live.subject._record_offsets = True
    with live:
        live.wait_round(1)
        _edit_in_place(f"{d}/a.txt", b"a two")
        os.unlink(f"{d}/c.txt")
        _put(f"{d}/d.txt", b"d one")
        live.wait_event(("insert", "d.txt", b"d one"))
        _edit_in_place(f"{d}/d.txt", b"d two")
        _put(f"{d}/e.txt", b"e one")
        live.wait_event(("insert", "e.txt", b"e one"))
        for k in (2, 3):
            live.core.permits.release()
            live.wait_round(k)
        os.unlink(f"{d}/e.txt")
        live.wait_event(("delete", "e.txt", b"e one"))
    assert live.error is None
    batches = live.batches()
    assert _net(live.events()) == live.on_disk()
    commits = [c for c in live.commits if c[0] is not None]
    assert sorted({n for _offsets, n in commits}) == list(range(1, len(batches) + 1))
    monkeypatch.setattr(fs_mod, "_native_core", _built_core)
    for offsets, n in commits:
        restored = _fs_subject(d, "binary")
        restored.seek(pickle.loads(pickle.dumps(offsets)))
        restored._scan_once()
        rest = [(op, os.path.relpath(values[1].value["path"], d), values[0])
                for batch in restored._committed for op, _key, values in batch]
        before = [event for batch in batches[:n] for event in batch]
        assert _net(before + rest) == live.on_disk(), n
        assert restored._scan_once() is False


@needs_native
def test_fs_run_fault_of_a_round_reaches_the_supervisor(tmp_path, monkeypatch):
    """(e): a fault in ``stat_files`` on the verify thread is raised by
    ``run()``; the supervisor registers it and starts ``run()`` again, whose
    first poll and whose rounds verify again; ``close()`` ends both threads."""
    from pathway_tpu.internals.errors import error_stats

    monkeypatch.setenv("PATHWAY_CONNECTOR_BACKOFF_S", "0.01")
    d = _watched(tmp_path)
    live = _Live(d, monkeypatch, _LiveCore(_built_core), supervised=True)
    errors = error_stats().get("connector", 0)
    with live:
        live.wait_round(1)
        live.core.fault = OSError("stat_files failed")
        live.core.permits.release()
        # the restart: a poll of both passes on run()'s thread, then rounds again
        live.wait("the first poll of the restart",
                  lambda: live.core.count("listing", "stat_files") >= 1)
        _edit_in_place(f"{d}/b.txt", b"b two")
        live.wait_round(2)
        live.core.permits.release()
        live.wait_round(3)
        assert live.supervisor.restarts == 1
    assert live.error is None
    assert error_stats()["connector"] == errors + 1
    assert live.batches()[1:] == [[("delete", "b.txt", b"b one"), ("insert", "b.txt", b"b two")]]
    assert _net(live.events()) == live.on_disk()


def test_fs_run_python_lister_is_one_thread(tmp_path, monkeypatch):
    """(f): without the native core a poll is one pass on run()'s thread."""
    d = _watched(tmp_path)
    core = _LiveCore(None)
    live = _Live(d, monkeypatch, core)
    assert fs_mod._native_core is None
    with live:
        live.wait("three polls", lambda: core.count("listing", "pause") >= 3)
        _edit_in_place(f"{d}/b.txt", b"b two")
        _put(f"{d}/d.txt", b"d one")
        live.wait_event(("insert", "b.txt", b"b two"))
        live.wait_event(("insert", "d.txt", b"d one"))
        assert [t.name for t in threading.enumerate() if t.name.endswith("-verify")] == []
    assert live.error is None
    assert {c[:2] for c in core.calls} == {("listing", "pause")}
    assert _net(live.events()) == live.on_disk()


@needs_native
def test_fs_run_both_passes_under_churn(tmp_path, monkeypatch):
    """Both threads free, the interpreter switching between them as often as
    it can, while the directory churns: every commit holds whole files, the
    index ends at the directory, ``_seen`` too."""
    import sys

    d = _watched(tmp_path, names=[f"f{i:02d}" for i in range(24)])
    core = _LiveCore(_built_core, stat_first=False)
    core.free()
    live = _Live(d, monkeypatch, core)
    monkeypatch.setattr(live.subject, "refresh_s", 0.0005)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with live:
            for step in range(240):
                name = f"{d}/f{step % 31:02d}.txt"
                if step % 5 == 4 and os.path.exists(name):
                    os.unlink(name)
                elif step % 2 and os.path.exists(name):
                    _edit_in_place(name, (b"%d " % step * 8)[:os.stat(name).st_size])
                else:
                    _put(name, f"f{step % 31:02d} put {step}".encode())
            final = live.on_disk()
            live.wait("two more rounds", lambda n=core.count("verify", "stat_files"):
                      core.count("verify", "stat_files") >= n + 2)
            live.wait("the directory in the index", lambda: _net(live.events()) == final)
    finally:
        sys.setswitchinterval(interval)
    assert live.error is None
    for batch in live.batches():  # a changed file's rows leave and come in one commit
        for op, name, _data in batch:
            if op == "delete":
                others = [o for o, n, _ in batch if n == name]
                assert others in (["delete"], ["delete", "insert"]), batch
    assert sorted(os.path.relpath(p, d) for p in live.subject._seen) == [n for n, _ in final]
