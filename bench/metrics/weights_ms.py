"""Device milliseconds in the ``compress/weights`` scope per compression."""


def read(ctx):
    t = ctx["reduced"].scope_s("compress/weights")
    if not t:
        return None
    return 1e3 * t / ctx["units"]
