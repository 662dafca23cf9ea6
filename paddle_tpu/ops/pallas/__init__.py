"""Pallas TPU kernels (flash attention, fused norms)."""
import jax
from jax.experimental import pallas as pl


def named_pallas_call(name, kernel, **kwargs):
    """`pl.pallas_call(kernel, **kwargs)` whose launch reads `name` in a
    device trace.  XLA names a kernel's op after the innermost scope of
    JAX's name stack, and under `jax.grad` the innermost scope is folded
    into the transform's own (`name=` alone, or a scope alone, reads
    `jvp_<name>_`; an unnamed kernel read `jvp__`).  Two nested scopes of
    the same name leave the inner one standing: `name=` opens one and
    labels the Mosaic kernel, `jax.named_scope` around the call the
    other (compiled for a described v5e, PR 24)."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def launch(*operands):
        with jax.named_scope(name):
            return call(*operands)

    return launch
