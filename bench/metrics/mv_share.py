"""Device time inside the program's ``mv`` named scope over busy time, in %:
every forward operator application (Φx, Φ̂ g_Γ, Φ̂(x⁺−x) and the residual
products), whatever operator implements it. Read through
``bench/scopes.py``; None where the trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "mv", "mv_share")
