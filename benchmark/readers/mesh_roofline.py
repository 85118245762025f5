"""Share of its roofline a program reached on one device of a mesh, in
%: the least time one chip could take for the device's own part of the
work (``work`` names a function of ``benchmark/lib/mesh_roofline.py``,
``shapes`` are its arguments) over the longest single event of the
program in the trace, on the device that spent most on it. Nothing to
read gives nothing, never 0."""

from benchmark.lib import cells, mesh_roofline, roofline
from benchmark.readers.trace_program_time import program_seconds


def read(args: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    took = program_seconds(ctx["trace"], args["match"], "event")
    if not took:
        return None
    work = getattr(mesh_roofline, args["work"])(**args["shapes"])
    least = roofline.least_seconds(work, cells.peaks(ctx["device_kind"]))
    ctx["notes"].append({"roofline": args["work"], "shapes": args["shapes"],
                         "bytes": work["total"], "bound": least["bound"],
                         "least_s": least["seconds"], "took_s": took})
    return 100.0 * least["seconds"] / took
