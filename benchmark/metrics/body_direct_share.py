"""Served request: percent of the body bytes handed to handlers in the window that the socket read straight into the handler's buffer, with no copy on the event loop (kernel-stats.body_read: delta of direct / delta of bytes). None on a program without the counter, or in a window that read no body."""


def _counts(ks):
    got = ks.get("body_read") if isinstance(ks, dict) else None
    return got if isinstance(got, dict) and "direct" in got else None


def read(run):
    a, b = _counts(run.ks_open), _counts(run.ks_close)
    if a is None or b is None:
        return None
    nbytes = b["bytes"] - a["bytes"]
    return 100.0 * (b["direct"] - a["direct"]) / nbytes if nbytes > 0 else None
