"""Device milliseconds of the prefill chunk programs per 1000 prompt
tokens they computed, in the traced slice."""

import work


def read(run):
    n = work.chunk_tokens(run)
    s = work.program_s(run, work.CHUNK)
    return s * 1e3 / (n / 1000) if n and s else None
