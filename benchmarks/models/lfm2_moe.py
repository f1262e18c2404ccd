"""Builder of the LFM2-MoE configurations: the benchmark's seeded weights
and batch, the program's next-token loss (``grace_tpu.models.lfm2``) and
the plain reference's (``benchmarks.reference.lfm2_moe``), on the same
weights."""

import functools

import jax.numpy as jnp

from benchmarks.reference import lfm2_moe as plain
# At the top, so that a program without the model fails when the builder is
# loaded, before any weight is made.
from grace_tpu.models import lfm2

init = plain.init
make_batch = plain.make_batch


def model_config(sizes):
    """The program's ``Config`` of the share the configuration states."""
    lay = plain.layout(sizes)
    return lfm2.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        layer_types=tuple(lay["kinds"]),
        num_dense_layers=sizes["num_dense_layers"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_experts=lay["router"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        first_expert=lay["first"], experts_held=sizes["num_experts"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=lay["head_dim"], conv_L_cache=sizes["conv_L_cache"],
        rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
        norm_eps=sizes["norm_eps"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]))


def program_loss(sizes):
    cfg = model_config(sizes)
    dtype = jnp.dtype(sizes["activation_dtype"])

    def loss_fn(params, mstate, batch):
        return lfm2.next_token_loss(params, mstate, batch, cfg, dtype=dtype)

    return loss_fn


def reference_loss(sizes):
    return functools.partial(
        plain.loss, sizes=sizes,
        activation_dtype=jnp.dtype(sizes["activation_dtype"]))
