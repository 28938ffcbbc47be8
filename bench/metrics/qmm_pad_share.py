"""Device time inside the program's ``qmm_pad`` named scope over busy time, in
%: the packed kernel's per-call padded copies of x, the codes and the scales
(``kernels/qmm/ops.py``). Read through ``bench/scopes.py``; None where the
trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "qmm_pad", "qmm_pad_share")
