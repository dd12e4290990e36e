"""Device milliseconds in the grid<->tree permutations of the operator
(scopes ``solve/transpose-in`` and ``solve/transpose-out``) per PCG
iteration."""


def read(ctx):
    t = ctx["reduced"].scope_s("solve/transpose-in", "solve/transpose-out")
    if not t or not ctx.get("iterations"):
        return None
    return 1e3 * t / ctx["iterations"]
