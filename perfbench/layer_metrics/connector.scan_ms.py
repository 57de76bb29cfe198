"""Mean time of a directory scan that emitted at least one file: listing and
stat of the watched path, reading and parsing the new files, the commit
(``io/fs`` ``_scan_once`` under the span ``connector.scan``;
``pathway_request_stage_ms{stage="connector.scan"}`` sum / count over the
window).  An empty poll is not observed."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.connector.scan.count", 0)
    return d["stage.connector.scan.sum"] / n if n else None
