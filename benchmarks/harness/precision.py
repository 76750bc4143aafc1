"""The control's precision, the step below a configuration's bfloat16."""


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax / 448), as
    float8 training does. The gradient passes straight through the
    rounding, so a reference's backward pass runs on the rounded values
    rather than stopping at the cast."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0)
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)
