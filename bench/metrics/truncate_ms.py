"""Device milliseconds in the truncation upsweep (scope
``compress/truncate``: the leaf and inner SVDs and the slices to the picked
ranks) per compression."""


def read(ctx):
    t = ctx["reduced"].scope_s("compress/truncate")
    if not t:
        return None
    return 1e3 * t / ctx["units"]
