"""Share of its roofline a program reached, in %: the least time the
chip could take for the work (``work`` names a function of
``benchmark/lib/roofline.py``; its arguments come from the metric's file
and, where a value is ``{"config": "<dotted path>"}``, from the cell's
configuration, or ``{"traffic_series": "<type>"}``, the series of that
type the mix keeps live in an interval, as each group's kind says) over
the longest single event of the program in the trace. Nothing to read
gives nothing, never 0."""

from benchmark import kinds
from benchmark.lib import cells, roofline
from benchmark.readers.trace_program_time import program_seconds
from benchmark.readers.vars_path import _dig


def read(args: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    took = program_seconds(ctx["trace"], args["match"], "event")
    if not took:
        return None
    shapes = {}
    for key, value in args["shapes"].items():
        if isinstance(value, dict) and "traffic_series" in value:
            value = sum(kinds.of(g).live_series(g)
                        for g in ctx["traffic"]["groups"]
                        if g["type"] == value["traffic_series"])
        elif isinstance(value, dict):
            value = _dig(ctx["config"], value["config"])
            if isinstance(value, list):
                value = len(value)
        shapes[key] = value + int(args.get("add", {}).get(key, 0))
    work = getattr(roofline, args["work"])(**shapes)
    least = roofline.least_seconds(work, cells.peaks(ctx["device_kind"]))
    ctx["notes"].append({"roofline": args["work"], "shapes": shapes,
                         "bytes": work["total"], "bound": least["bound"],
                         "least_s": least["seconds"], "took_s": took})
    return 100.0 * least["seconds"] / took
