"""Share of the memory roofline: the least time in which the device could
move the bytes the replayed gates must read and write (counted from the
compiled trace by ``chipbench.roofline``), over the device time of the
replay (words and seconds as ``chipbench.roofline.replayed`` reads them)."""
from chipbench.roofline import replayed, roofline_pct


def read(ctx):
    got = replayed(ctx.get("trace"), ctx.get("words_per_call"))
    if got is None or not ctx.get("word_bytes"):
        return None
    words, seconds = got
    return roofline_pct(words * ctx["word_bytes"], seconds,
                        ctx["device_kind"])
