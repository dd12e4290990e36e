"""Mean PCG iterations per solve, from the solver's own iteration count."""


def read(ctx):
    if not ctx.get("iterations"):
        return None
    return ctx["iterations"] / ctx["units"]
