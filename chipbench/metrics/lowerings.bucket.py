"""JAX lowerings (jit cache misses, the program's ``jax_lowerings_total``)
over the profiled window: 0 where every program shape was warmed up."""

from chipbench import spans


def read(run):
    return spans.lowerings()
