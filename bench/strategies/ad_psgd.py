"""Ring AD-PSGD (paper Eq. 14, ``W_{k+1} = W_k T_1 - lr g(W_{k-1})``):
learner replicas stacked on the parameters, each mixed with its two ring
neighbours, gradients at the previous iterate (``W_{-1} = W_0``)."""
import jax.numpy as jnp

STACKED = True      # one parameter replica per learner, stacked on axis 0
STALE = True        # the gradient is taken at the previous iterate


def mix(p: dict) -> dict:
    """T_1: each replica averaged with its ring neighbours in float32,
    then held in its own dtype again."""
    def one(w):
        L = w.shape[0]
        if L == 1:
            return w
        wf = w.astype(jnp.float32)
        if L == 2:
            m = (2.0 * wf + jnp.roll(wf, 1, 0)) / 3.0
        else:
            m = (wf + jnp.roll(wf, 1, 0) + jnp.roll(wf, -1, 0)) / 3.0
        return m.astype(w.dtype)
    return {k: one(w) for k, w in p.items()}
