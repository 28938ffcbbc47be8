"""Device time inside the program's ``fft`` named scope over busy time, in %:
the FFTs of the subsampled Fourier operator. Read through
``bench/scopes.py``; None where the trace holds no such scope."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "fft", "fft_share")
