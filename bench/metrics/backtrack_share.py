"""Device time inside the program's ``backtrack`` named scope over busy time,
in %: the step-size rounds of backtracking, with their operator applications
and H_s. Read through ``bench/scopes.py``; None where the trace holds no
such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "backtrack", "backtrack_share")
