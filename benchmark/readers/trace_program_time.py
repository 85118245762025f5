"""Device seconds of the programs whose module name holds one of
``match``, in the traced window, on the device that spent most on them.
``per``: ``window`` (the sum), ``event`` (the longest single event) or
``event_mean`` (the mean over events at least a tenth as long as the
longest: the program at the deployment's shape, not at a small group's)."""


def program_seconds(trace: dict, match: list, per: str):
    best = None
    for dev in trace["devices"]:
        durations = [d for name, ds in dev["programs"].items()
                     if any(m in name for m in match) for d in ds]
        if not durations:
            continue
        if per == "event":
            value = max(durations)
        elif per == "event_mean":
            full = [d for d in durations if d >= 0.1 * max(durations)]
            value = sum(full) / len(full)
        else:
            value = sum(durations)
        best = value if best is None else max(best, value)
    return best


def read(args: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    return program_seconds(ctx["trace"], args["match"],
                           args.get("per", "window"))
