"""Device: the process's peak device memory in use after the window
(`memory_stats()["peak_bytes_in_use"]`), GiB. Moves
output_tokens_per_s: memory caps the slots and the cache."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**30
