"""Pure-jnp oracle for the xtx kernel."""

import jax
import jax.numpy as jnp


def xtx_xty_ref(x, y):
    """(N,K),(N,) -> (K,K) f32, (K,) f32."""
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST   # as the kernel's MXU passes
    return (jnp.matmul(x32.T, x32, precision=hi),
            jnp.matmul(x32.T, y32, precision=hi))
