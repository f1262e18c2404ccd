"""Builder of the SDAR configurations (a Qwen3-shaped expert decoder trained
as a block diffusion model): the benchmark's seeded weights and batch
(clean tokens and a key a sequence for the step's noise), the program's
block-diffusion loss (``grace_tpu.models.sdar``) and the plain reference's
(``benchmarks.reference.sdar_moe``), on the same weights."""

import functools

import jax.numpy as jnp

from benchmarks.reference import sdar_moe as plain
# At the top, so that a program without the model fails when the builder is
# loaded, before any weight is made.
from grace_tpu.models import sdar

init = plain.init
make_batch = plain.make_batch


def model_config(sizes):
    """The program's ``Config`` of the share the configuration states."""
    lay = plain.layout(sizes)
    return sdar.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_experts=lay["router"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        first_expert=lay["first"], experts_held=sizes["num_experts"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], rope_theta=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"], block_length=sizes["block_length"],
        noise_eps=sizes["noise_eps"])


def program_loss(sizes):
    cfg = model_config(sizes)
    dtype = jnp.dtype(sizes["activation_dtype"])

    def loss_fn(params, mstate, batch):
        return sdar.block_diffusion_loss(params, mstate, batch, cfg,
                                         dtype=dtype)

    return loss_fn


def reference_loss(sizes):
    return functools.partial(plain.loss, sizes=sizes)
