"""Device milliseconds per distributed H^2 application in its exchange,
averaged over the chips: the scopes ``hgemv/exchange`` (the halo-plan
packing ``halo/pack`` and rounds ``halo/round`` inside it),
``hgemv/root-gather`` (the branch roots' ``all_gather``) and ``halo/*``
(``halo/land``, the landed buffers' slicing)."""


def read(ctx):
    red = ctx["reduced"]
    if not ctx.get("matvecs") or not red.has_scope("hgemv/exchange"):
        return None
    return 1e3 * red.scope_s("hgemv/exchange", "hgemv/root-gather",
                             "halo") / ctx["matvecs"]
