"""Device time inside the program's ``yquant`` named scope over busy time, in
%: the per-band quantization of the observations y, one program a chunk.
Read through ``bench/scopes.py``; None where the trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "yquant", "yquant_share")
