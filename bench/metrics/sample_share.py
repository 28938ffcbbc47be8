"""Device time inside the program's ``sample`` named scope over busy time, in
%: k-space sampling: the gather of the subsampled Fourier operator's ``mv``
and the zero-fill scatter of its ``rmv``. Read through ``bench/scopes.py``;
None where the trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "sample", "sample_share")
