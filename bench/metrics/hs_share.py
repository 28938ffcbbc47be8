"""Device time inside the program's ``threshold`` named scope over busy time,
in %: all of H_s: the top-s selections, the fusions and the mask scatters
around them. Read through ``bench/scopes.py``; None where the trace holds no
such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "threshold", "hs_share")
