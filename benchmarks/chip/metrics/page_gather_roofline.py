"""Kernels: ``kernels/page_migrate.py``'s gather kernel, share of its
roofline over the traced part: every migrated page's payload in every
store, read once and written once to the staging buffer, as the
architecture's adapter counts it (``run.work.migrate_bytes``), over the
kernel's device time, in %."""

KERNEL = "page_gather"


def read(run):
    if run.trace is None:
        return None
    pages = run.traced_counters.get("migrated_pages", 0)
    seconds = sum(e.dur for e in run.trace.calls(run.trace.ops, lambda n: KERNEL in n)) / 1e9
    if not pages or seconds <= 0:
        return None
    nbytes = run.work.migrate_bytes(pages)
    return run.counts.roofline_share(0.0, nbytes, seconds, run.peak)[0]
