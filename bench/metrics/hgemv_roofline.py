"""Share of the roofline reached by the H^2 applications, in percent.

Least time of one application: the larger of its model flops over the f32
compute peak and its least bytes over the HBM bandwidth
(``bench/cost_model.py``, ``bench/peaks.json``), times the applications in
the window, over the device time in the ``hgemv/*`` scopes.
"""
from bench import cost_model


def read(ctx):
    t = ctx["reduced"].scope_s("hgemv")
    if not t or not ctx.get("matvecs"):
        return None
    shape, nv = ctx["matvec_shape"], ctx["nv"]
    least, _ = cost_model.least_time(cost_model.h2_matvec_flops(shape, nv),
                                     cost_model.h2_matvec_bytes(shape, nv),
                                     ctx["peaks"])
    return 100.0 * least * ctx["matvecs"] / t
