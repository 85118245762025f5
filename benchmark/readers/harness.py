"""A number the harness itself measured (the sender's lag, ...)."""


def read(args: dict, ctx: dict):
    value = ctx["harness"].get(args["key"])
    return None if value is None else float(value) * float(
        args.get("scale", 1.0))
