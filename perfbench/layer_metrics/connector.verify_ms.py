"""Mean time of the second pass of a poll of the watched path: one
``fstatat`` of every file that was known before the poll, compared by
(mtime, size), and the re-read and commit of those that changed (``io/fs``
``_scan_and_emit`` under the span ``connector.verify``;
``pathway_request_stage_ms{stage="connector.verify"}`` sum / count over the
window).  It runs after the poll's new files were committed, so it is part
of the period between two polls and of no new file's way.  Every poll that
ran the pass is observed, whether it found a changed file or not."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.connector.verify.count", 0)
    return d["stage.connector.verify.sum"] / n if n else None
