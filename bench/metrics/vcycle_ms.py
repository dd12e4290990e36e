"""Device milliseconds in the ``precond/vcycle`` scope per PCG iteration."""


def read(ctx):
    t = ctx["reduced"].scope_s("precond/vcycle")
    if not t or not ctx.get("iterations"):
        return None
    return 1e3 * t / ctx["iterations"]
