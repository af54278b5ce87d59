"""Seconds JAX spent tracing, lowering and compiling (or loading a cached
program) inside the window, per federation: what the facade pays again
on every call because a program is traced anew (``jax.monitoring``)."""


def read(layer):
    fits = layer.get("fits")
    if not fits:
        return None
    return sum(f["compile_s"] for f in fits) / len(fits)
