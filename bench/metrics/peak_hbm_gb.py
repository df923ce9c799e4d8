"""Peak device memory in use after the window, GB (1e9 bytes), on the
fullest chip (`memory_stats()["peak_bytes_in_use"]`)."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
