"""Crossbar images per engine batch the service issued in the window."""


def read(ctx):
    if not ctx.get("batches"):
        return None
    return ctx["units"] / ctx["batches"]
