"""Seconds JAX spent compiling or loading programs during set-up (its own
compile events), as counted where the window opened."""


def read(ctx):
    return ctx["setup"].get("compile_s")
