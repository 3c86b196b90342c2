"""A counter family's move over the measured window.

args: ``family``, ``label`` (substring of the label string; default
all children).  A family the program never touched reads 0.
"""


def read(args: dict, ctx):
    return ctx.window.counter_delta(args["family"], args.get("label", ""))
