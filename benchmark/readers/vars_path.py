"""A value of ``/debug/vars`` by its dotted path (list indices as
numbers). ``at``: ``start`` or ``end`` of the window, ``delta`` between
them, or ``poll_max`` over the 1 Hz polls."""


def _dig(obj, path: str):
    for part in path.split("."):
        if isinstance(obj, list):
            if not part.isdigit() or int(part) >= len(obj):
                return None
            obj = obj[int(part)]
        elif isinstance(obj, dict) and part in obj:
            obj = obj[part]
        else:
            return None
    return obj


def polled(args: dict) -> list:
    """The section of ``/debug/vars`` that every poll has to keep for
    this metric."""
    return [args["path"].split(".")[0]] if args["at"] == "poll_max" else []


def read(args: dict, ctx: dict):
    at, path = args["at"], args["path"]
    if at in ("start", "end"):
        value = _dig(ctx["vars_" + at], path)
    elif at == "delta":
        a, b = _dig(ctx["vars_start"], path), _dig(ctx["vars_end"], path)
        value = None if a is None or b is None else b - a
    elif at == "poll_max":
        seen = [_dig(p, path) for p in ctx["polls"]]
        seen = [v for v in seen if v is not None]
        value = max(seen) if seen else None
    else:
        raise ValueError(f"vars_path: no at={at!r}")
    if value is None:
        return None
    return float(value) * float(args.get("scale", 1.0))
