"""The least time the chip could take for the traced calls, by the kernel's
ops-and-bytes function and the peaks table, over the time its module(s)
took on the device."""

from . import module_time
from .. import files


def read(ctx, p):
    n, s = module_time(ctx, p["modules"])
    if not n or not ctx.get("calls") or not ctx.get("peaks"):
        return None     # no trace of the kernel, or no chip whose peaks count
    least = 0.0
    for call in ctx["calls"]:
        flops, nbytes = files.kernel(p["kernel"]).ops_and_bytes(call)
        least += max(flops / ctx["peaks"]["flops_per_s"],
                     nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / s
