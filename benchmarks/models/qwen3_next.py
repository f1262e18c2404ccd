"""Builder of the Qwen3-Next configurations (gated delta-rule layers, three
in four, beside output-gated softmax attention at heads of 256; 512 experts
ten a token beside a gated shared expert): the benchmark's seeded weights
and batch, the program's next-token loss (``grace_tpu.models.qwen3_next``)
and the plain reference's (``benchmarks.reference.qwen3_next``), on the same
weights."""

import functools

import jax.numpy as jnp

from benchmarks.reference import qwen3_next as plain
# At the top, so that a program without the model fails when the builder is
# loaded, before any weight is made.
from grace_tpu.models import qwen3_next

init = plain.init
make_batch = plain.make_batch


def model_config(sizes):
    """The program's ``Config`` of the share the configuration states: the
    layers held take their kinds from ``full_attention_interval``."""
    lay = plain.layout(sizes)
    return qwen3_next.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        layer_types=tuple(
            "full_attention" if plain.is_full(sizes, i) else "linear_attention"
            for i in plain.layers_held(sizes)),
        moe_intermediate_size=sizes["moe_intermediate_size"],
        shared_expert_intermediate_size=sizes[
            "shared_expert_intermediate_size"],
        num_experts=lay["router"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        first_expert=lay["first"], experts_held=sizes["num_experts"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        rotary_dim=int(sizes["head_dim"] * sizes["partial_rotary_factor"]),
        rope_theta=float(sizes["rope_theta"]),
        linear_num_key_heads=sizes["linear_num_key_heads"],
        linear_num_value_heads=sizes["linear_num_value_heads"],
        linear_key_head_dim=sizes["linear_key_head_dim"],
        linear_value_head_dim=sizes["linear_value_head_dim"],
        linear_conv_kernel_dim=sizes["linear_conv_kernel_dim"],
        norm_eps=sizes["rms_norm_eps"],
        published_layers=sizes["published"].get(
            "num_hidden_layers", sizes["num_hidden_layers"]))


def program_loss(sizes):
    cfg = model_config(sizes)
    dtype = jnp.dtype(sizes["activation_dtype"])

    def loss_fn(params, mstate, batch):
        return qwen3_next.next_token_loss(params, mstate, batch, cfg,
                                          dtype=dtype)

    return loss_fn


def reference_loss(sizes):
    return functools.partial(plain.loss, sizes=sizes)
