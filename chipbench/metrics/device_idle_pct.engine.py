"""Share of the traced window in which no operation ran on the device.
Nothing is read where the profiler dropped events inside the window: the
part it kept is not the window."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or trace["truncated"]:
        return None
    return trace["idle_pct"]
