"""Compile cache: programs built inside the window (backend compiles and
loads from the persistent cache, from JAX's monitoring events). Every
program the window uses is warmed in set-up, so it should read 0."""


def read(ctx):
    return ctx.run.compiles_in_window
