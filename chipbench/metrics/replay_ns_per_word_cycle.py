"""Device time of the replay per packed word and compiled cycle (words and
seconds as ``chipbench.roofline.replayed`` reads them from the trace)."""
from chipbench.roofline import replayed


def read(ctx):
    got = replayed(ctx.get("trace"), ctx.get("words_per_call"))
    if got is None or not ctx.get("cycles"):
        return None
    words, seconds = got
    return seconds * 1e9 / (words * ctx["cycles"])
