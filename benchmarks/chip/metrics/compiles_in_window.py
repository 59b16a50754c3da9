"""Device (TPU v5e): JAX compilations, or loads from the persistent cache,
that happened inside the measured window (JAX's backend-compile events).
Moves queries_per_s."""


def read(w):
    return float(len(w.compiles))
