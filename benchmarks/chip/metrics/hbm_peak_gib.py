"""Peak device memory of the run, from the fullest chip's
``memory_stats()['peak_bytes_in_use']`` read right after the window."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 2**30 if b > 0 else None
