"""Device time inside the program's ``dwt`` named scope over busy time, in %:
the Haar pyramid of the wavelet synthesis operator and its adjoint. Read
through ``bench/scopes.py``; None where the trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "dwt", "dwt_share")
