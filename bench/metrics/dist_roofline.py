"""Share of the roofline reached by one chip's share of the distributed
H^2 applications, in percent.

Least time of one chip's share of one application: the larger of its
model flops over the f32 compute peak and its least bytes over the HBM
bandwidth (``bench/dist_cost_model.py``, ``bench/peaks.json``), times the
applications in the window, over the chip's device time in the
``hgemv/*`` scopes (averaged over the chips)."""
from bench import cost_model, dist_cost_model


def read(ctx):
    t = ctx["reduced"].scope_s("hgemv")
    if not t or not ctx.get("matvecs") or not ctx.get("chips"):
        return None
    shape, p, nv = ctx["matvec_shape"], ctx["chips"], ctx["nv"]
    least, _ = cost_model.least_time(
        dist_cost_model.share_flops(shape, p, nv),
        dist_cost_model.share_bytes(shape, p, nv), ctx["peaks"])
    return 100.0 * least * ctx["matvecs"] / t
