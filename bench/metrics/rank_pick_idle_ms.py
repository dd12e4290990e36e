"""Device idle milliseconds per compression while the host picks ranks:
the gaps of the device's busy time that fall inside the program's host
spans ``compress/rank-pick`` (each a device-to-host read of singular
values and the integer pick made from them)."""
from bench.trace_reduce import union

SPAN = "compress/rank-pick"


def read(ctx):
    red = ctx["reduced"]
    picks = union((s, e) for s, e, n in red.host if n == SPAN and
                  e > red.t0 and s < red.t1)
    if not picks or not ctx.get("units"):
        return None
    idle, i = 0.0, 0
    for s, e in red.gaps(0):
        while i < len(picks) and picks[i][1] <= s:
            i += 1
        j = i
        while j < len(picks) and picks[j][0] < e:
            idle += min(e, picks[j][1]) - max(s, picks[j][0])
            j += 1
    return 1e-6 * idle / ctx["units"]
