"""Megabytes (1e6 bytes) of schedule tiles that one chip holds as program
arguments: the program's counter `sharded.tile_bytes_per_device` over the
lowering of both sweeps.  Nothing is read from a program without the
counter."""


def read(ctx):
    got = ctx["counters"].get("tile_bytes_per_device")
    return got / 1e6 if got else None
