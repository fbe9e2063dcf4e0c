"""Bytes staged to the device an example: the sum of the window's passes'
``nbytes()`` over their records."""


def read(ctx):
    w = ctx["window"]
    return w["wire_bytes"] / w["records"]
