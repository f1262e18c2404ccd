"""Builder of the DeepSeek-V3-shaped configurations (latent attention,
shared and routed experts): the benchmark's seeded weights and batch, the
program's next-token loss (``grace_tpu.models.deepseek_v3``) and the plain
reference's (``benchmarks.reference.deepseek_v3``), on the same weights."""

import functools

import jax.numpy as jnp

from benchmarks.reference import deepseek_v3 as plain
# At the top, so that a program without the model fails when the builder is
# loaded, before any weight is made.
from grace_tpu.models import deepseek_v3

init = plain.init
make_batch = plain.make_batch


def model_config(sizes):
    """The program's ``Config`` of the share the configuration states."""
    lay = plain.layout(sizes)
    return deepseek_v3.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        first_k_dense_replace=sizes["first_k_dense_replace"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        n_shared_experts=sizes["n_shared_experts"],
        num_experts=lay["router"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        first_expert=lay["first"], experts_held=sizes["n_routed_experts"],
        num_attention_heads=sizes["num_attention_heads"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        route_eps=plain.ROUTE_EPS)


def program_loss(sizes):
    cfg = model_config(sizes)
    dtype = jnp.dtype(sizes["activation_dtype"])

    def loss_fn(params, mstate, batch):
        return deepseek_v3.next_token_loss(params, mstate, batch, cfg,
                                           dtype=dtype)

    return loss_fn


def reference_loss(sizes):
    return functools.partial(plain.loss, sizes=sizes)
