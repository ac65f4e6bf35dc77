"""core.mrbg_store: MRBG records read per delta row (read amplification,
a count): the store's bytes read (RunReport.io.bytes_read) over its
record size, summed over the window's refreshes, per delta row they took
in."""


def read(run):
    rs = run.window.refreshes
    rows = sum(r.rows for r in rs)
    if not rows:
        return None
    return sum(r.store_bytes_read / r.record_bytes for r in rs) / rows
