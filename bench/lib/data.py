"""The synthetic compendium, drawn on the device from the seed.

Gene i follows one of `programs` latent expression programs with a random
signed loading, plus its own noise: modules of correlated genes, as in a
real compendium, so a top-k search ranks real neighbours.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, beyond 32 bits too."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_compendium(seed: int, n: int, l: int, programs: int,
                    sharding: Optional[jax.sharding.Sharding] = None
                    ) -> jax.Array:
    """(n, l) float32 expression in one jitted call on the device:
    gene i = a_i * f_prog(i) + sqrt(1 - a_i^2) * noise_i, |a_i| in
    [0.3, 0.95]."""
    def draw(key):
        kp, ka, ks, kf, ke = jax.random.split(key, 5)
        prog = jax.random.randint(kp, (n,), 0, programs)
        sign = jnp.where(jax.random.bernoulli(ks, 0.5, (n,)), 1.0, -1.0)
        load = sign * jax.random.uniform(ka, (n,), minval=0.3, maxval=0.95)
        factors = jax.random.normal(kf, (programs, l))
        noise = jax.random.normal(ke, (n, l))
        return (load[:, None] * factors[prog]
                + jnp.sqrt(1.0 - load * load)[:, None] * noise)
    fn = jax.jit(draw, out_shardings=sharding)
    return jax.block_until_ready(fn(seed_key(seed)))
