"""Builder of the ResNet configurations: the benchmark's seeded weights and
batch, the program's loss (``grace_tpu.models.resnet``) and the plain
reference's (``benchmarks.reference.resnet50``), on the same weights."""

import functools

import jax.numpy as jnp
import optax

from benchmarks.reference import resnet50 as plain

init = plain.init
make_batch = plain.make_batch


def program_loss(sizes):
    from grace_tpu.models import resnet

    dtype = jnp.dtype(sizes["activation_dtype"])

    def loss_fn(params, mstate, batch):
        x, y = batch
        logits, new_mstate = resnet.apply(params, mstate, x.astype(dtype),
                                          train=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y)
        return loss.mean(), new_mstate

    return loss_fn


def reference_loss(sizes):
    return functools.partial(
        plain.loss, sizes=sizes,
        activation_dtype=jnp.dtype(sizes["activation_dtype"]))
