"""Share of its roofline one dispatch of a sketch's ingest reached, in
%: the least time the chip could take for the dispatch's bytes
(``work`` names a function of ``benchmark/lib/sketch_roofline.py``)
over the program's device time in the trace (``per`` as
``readers/trace_program_time.py`` has it). A shape is a number, or
taken from the cell: ``{"config": "<dotted path>"}`` from the
configuration, ``{"traffic_series": "<type>"}`` the live series of that
type, ``{"traffic_lines": "<kind>"}`` the lines a round that the mix's
groups of that kind send, ``{"timeline": "<dotted path>"}`` a field of
the flush timeline's entries, its mean over the window's flushes. With
``per_timeline`` beside it the value is divided by that field's mean:
a count an interval becomes a count a dispatch. A program whose
timeline lacks the field (one from before the counter was there), or a
trace without the program, gives nothing, never 0."""

from benchmark import kinds
from benchmark.lib import cells, roofline, sketch_roofline
from benchmark.readers.trace_program_time import program_seconds
from benchmark.readers.vars_path import _dig


def _timeline_mean(ctx: dict, path: str):
    seen = [_dig(entry, path) for entry in ctx["timeline"]]
    seen = [float(v) for v in seen if v is not None]
    return sum(seen) / len(seen) if seen else None


def _shape(value, ctx: dict):
    if not isinstance(value, dict):
        return value
    if "config" in value:
        out = _dig(ctx["config"], value["config"])
    elif "traffic_series" in value:
        out = sum(kinds.of(g).live_series(g)
                  for g in ctx["traffic"]["groups"]
                  if g["type"] == value["traffic_series"])
    elif "traffic_lines" in value:
        out = sum(int(g["lines"]) for g in ctx["traffic"]["groups"]
                  if g.get("kind") == value["traffic_lines"])
    else:
        out = _timeline_mean(ctx, value["timeline"])
    if out is None or "per_timeline" not in value:
        return out
    per = _timeline_mean(ctx, value["per_timeline"])
    return out / per if per else None


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
    work = getattr(sketch_roofline, args["work"])(**shapes)
    least = roofline.least_seconds(work, cells.peaks(ctx["device_kind"]))
    ctx["notes"].append({"roofline": args["work"], "shapes": shapes,
                         "bytes": work["total"], "bound": least["bound"],
                         "least_s": least["seconds"], "took_s": took})
    return 100.0 * least["seconds"] / took
