"""Host time in the service's load and decode spans per completed request."""


def read(ctx):
    if not ctx.get("requests") or not ctx.get("host_span_s"):
        return None
    return ctx["host_span_s"] * 1e3 / ctx["requests"]
