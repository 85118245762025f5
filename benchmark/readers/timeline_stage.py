"""Mean over the window's flushes of the seconds the flush timeline
gives the named stages (summed where a stage occurs more than once in a
flush, as the streamed sink's per-chunk stages do)."""


def read(args: dict, ctx: dict):
    names = set(args["stages"])
    per_flush = []
    for entry in ctx["timeline"]:
        found = [s["duration_ns"] for s in entry["stages"]
                 if s["name"] in names]
        if found:
            per_flush.append(sum(found) / 1e9)
    if not per_flush:
        return None
    return sum(per_flush) / len(per_flush)
