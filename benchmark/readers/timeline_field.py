"""A field of the flush timeline's entries by its dotted path, mean (or
``reduce``: ``max``) over the window's flushes."""

from benchmark.readers.vars_path import _dig


def read(args: dict, ctx: dict):
    seen = [_dig(entry, args["path"]) for entry in ctx["timeline"]]
    seen = [float(v) for v in seen if v is not None]
    if not seen:
        return None
    value = max(seen) if args.get("reduce") == "max" else sum(seen) / len(
        seen)
    return value * float(args.get("scale", 1.0))
