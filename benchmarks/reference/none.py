"""Plain reference of the dense exchange: the replicas' gradients are
averaged, nothing is compressed and there is no state."""

import jax.numpy as jnp


def init_state(shape, key, world, spec):
    return None


def exchange(grads, state, spec):
    """``grads``: one leaf stacked over replicas ``(W, ...)``. Returns the
    gradient every replica applies and the new state."""
    return jnp.mean(grads, axis=0), state


def seeded(state):
    """The part of the start state that is drawn from the seed, which the
    program has to be handed: none."""
    return None
