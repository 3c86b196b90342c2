"""One number of ``device.memory_stats()`` of the fullest device.

args: ``key``, ``scale``.
"""


def read(args: dict, ctx):
    if args["key"] not in ctx.memory:
        return None
    return ctx.memory[args["key"]] * args.get("scale", 1.0)
