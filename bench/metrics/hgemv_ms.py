"""Device milliseconds in the ``hgemv/*`` scopes per H^2 application (per
PCG iteration in a solve: one application each)."""


def read(ctx):
    t = ctx["reduced"].scope_s("hgemv")
    if not t or not ctx.get("matvecs"):
        return None
    return 1e3 * t / ctx["matvecs"]
