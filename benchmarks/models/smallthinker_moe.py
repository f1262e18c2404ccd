"""Builder of the SmallThinker configurations (windowed rotary layers beside
full-attention layers without positions, a router that reads the layer's
input before attention, ReLU-gated experts): the benchmark's seeded weights
and batch, the program's next-token loss (``grace_tpu.models.smallthinker``)
and the plain reference's (``benchmarks.reference.smallthinker_moe``), on the
same weights."""

import functools

import jax.numpy as jnp

from benchmarks.reference import smallthinker_moe as plain
# At the top, so that a program without the model fails when the builder is
# loaded, before any weight is made.
from grace_tpu.models import smallthinker

init = plain.init
make_batch = plain.make_batch


def model_config(sizes):
    """The program's ``Config`` of the share the configuration states: the
    layers held take their entries of the two published layouts."""
    lay = plain.layout(sizes)
    held = list(plain.layers_held(sizes))
    return smallthinker.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        sliding_window_layout=tuple(
            sizes["sliding_window_layout"][i] for i in held),
        rope_layout=tuple(sizes["rope_layout"][i] for i in held),
        sliding_window_size=sizes["sliding_window_size"],
        moe_intermediate_size=sizes["moe_ffn_hidden_size"],
        num_experts=lay["router"],
        num_experts_per_tok=sizes["moe_num_active_primary_experts"],
        first_expert=lay["first"],
        experts_held=sizes["moe_num_primary_experts"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], rope_theta=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"],
        published_layers=sizes["published"].get(
            "num_hidden_layers", sizes["num_hidden_layers"]))


def program_loss(sizes):
    cfg = model_config(sizes)
    dtype = jnp.dtype(sizes["activation_dtype"])

    def loss_fn(params, mstate, batch):
        return smallthinker.next_token_loss(params, mstate, batch, cfg,
                                            dtype=dtype)

    return loss_fn


def reference_loss(sizes):
    return functools.partial(plain.loss, sizes=sizes)
