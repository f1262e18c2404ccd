"""Builder of the BERT configurations: the benchmark's seeded weights and
batch, the program's SQuAD span loss (``grace_tpu.models.transformer``) and
the plain reference's (``benchmarks.reference.bert_base``), on the same
weights."""

import functools

import jax.numpy as jnp
import optax

from benchmarks.reference import bert_base as plain

init = plain.init
make_batch = plain.make_batch


def program_loss(sizes):
    from grace_tpu.models import layers, transformer

    dtype = jnp.dtype(sizes["activation_dtype"])
    cfg = transformer.Config(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_layers=sizes["num_hidden_layers"],
        d_ff=sizes["intermediate_size"], max_len=sizes["max_seq_length"],
        num_classes=2)

    def loss_fn(params, mstate, batch):
        ids, spans = batch
        x = transformer.encode(params, ids, cfg, dtype=dtype)
        logits = layers.dense_apply(params["cls"], x.astype(jnp.float32))
        loss = (optax.softmax_cross_entropy_with_integer_labels(
                    logits[..., 0], spans[:, 0])
                + optax.softmax_cross_entropy_with_integer_labels(
                    logits[..., 1], spans[:, 1]))
        return loss.mean(), mstate

    return loss_fn


def reference_loss(sizes):
    return functools.partial(
        plain.loss, sizes=sizes,
        activation_dtype=jnp.dtype(sizes["activation_dtype"]))
