"""Share of its roofline a sparse set program reached, in %: the least
time the chip could take for the bytes its work needs (``work`` names a
function of ``benchmark/lib/set_roofline.py``) over the program's device
time in the trace (``per`` as ``readers/trace_program_time.py`` has it).
A shape is a number or taken from the cell as
``readers/sketch_roofline.py`` takes it (a field of the flush timeline,
with ``per_timeline`` a count a dispatch). A program whose timeline
lacks the field (one from before the sparse store), or a trace without
the program, gives nothing, never 0."""

from benchmark.lib import cells, roofline, set_roofline
from benchmark.readers.sketch_roofline import _shape
from benchmark.readers.trace_program_time import program_seconds


def read(args: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    took = program_seconds(ctx["trace"], args["match"],
                           args.get("per", "event"))
    if not took:
        return None
    shapes = {key: _shape(value, ctx)
              for key, value in args["shapes"].items()}
    if any(v is None for v in shapes.values()):
        return None
    work = getattr(set_roofline, args["work"])(**shapes)
    least = roofline.least_seconds(work, cells.peaks(ctx["device_kind"]))
    ctx["notes"].append({"roofline": args["work"], "shapes": shapes,
                         "bytes": work["total"], "bound": least["bound"],
                         "least_s": least["seconds"], "took_s": took})
    return 100.0 * least["seconds"] / took
