"""Device time inside the program's ``rmv`` named scope over busy time, in %:
every adjoint application (Φ̂†r). Read through ``bench/scopes.py``; None
where the trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "rmv", "rmv_share")
