"""Set-up: process start to the window's start — loading, rendering the
seed's slides, standing up the deployment, and warming (compiling or
loading from the compile cache) every program the cell runs."""


def read(ctx):
    return ctx.setup_s
