"""The packed operator kernel's share of its roofline, in %: the least time
the chip could take for the operator work the algorithm needed (counted by
``bench/work.py`` from the configuration and the iterations run), over the
device time of the ``qmm`` Pallas calls. Both sides cover the same chunks:
those that ran while the profiler was on (``traced_counts``), not the rest
of the window."""
from bench import work, xplane

PATTERNS = (r"\bqmm",)


def read(ctx):
    t = xplane.seconds(ctx["trace"], PATTERNS)
    if t == 0 or not ctx["traced_counts"]:
        return None
    least, bound = work.least_seconds(ctx["cfg"], ctx["rows"] // ctx["shards"],
                                      ctx["traced_counts"], ctx["peak"])
    ctx["notes"].append(f"qmm_roofline_share: {bound}-bound, least "
                        f"{least} s over {t} s of qmm device time, "
                        f"{len(ctx['traced_counts'])} traced chunk shards")
    return 100.0 * least / t
