"""Device time of H_s's selections over busy time, in %: the ``TopK``
custom calls (s = 30 on the LOFAR path) and the ``sort`` ops that a top-8000
over 65,536 lowers to on the v5e (with the sort of the scatter that builds
the support mask). The fusions around them are not counted, so this is a
lower bound on H_s's share."""
from bench import xplane

PATTERNS = (r"^sort ", r"\bTopK\b")


def read(ctx):
    return xplane.share(ctx["trace"], PATTERNS)
