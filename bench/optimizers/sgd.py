"""Plain SGD: ``W - lr g`` in float32, held in the parameter's dtype."""
import jax.numpy as jnp


def update(p: dict, g: dict, lr) -> dict:
    return {k: (p[k].astype(jnp.float32) - lr * g[k]).astype(p[k].dtype)
            for k in p}
