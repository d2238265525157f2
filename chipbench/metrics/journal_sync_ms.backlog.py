"""Host milliseconds per acknowledged stripe spent in the journal's
fsyncs (catalog record, journal log, directory), from the harness's log
of every ``os.fsync`` over the traced window."""


def read(run):
    stripes = len(run.stamps.get("committed", []))
    if not stripes or "window_ns" not in run.stamps:
        return None
    n, seconds = run.syncs.seconds(*run.stamps["window_ns"],
                                   run.stamps["journal_dir"])
    if not n:
        return None
    return 1e3 * seconds / stripes
