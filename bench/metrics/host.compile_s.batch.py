"""Seconds the host spent making programs inside the window (s): tracing,
lowering, and compiling or loading them from JAX's persistent cache, as
``jax.monitoring`` reports them."""


def read(ctx):
    return ctx.compile_s
