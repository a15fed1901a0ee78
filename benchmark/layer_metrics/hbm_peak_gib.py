"""Residency: the allocator's `peak_bytes_in_use` on the fullest chip after
the window, in GiB."""


def read(ctx):
    return ctx["memory"]["peak_bytes"] / float(1 << 30)
