"""Seconds from the start of the process to the first step of the window:
loading, generating, compiling and warming up."""


def read(ctx):
    return ctx.setup_s
