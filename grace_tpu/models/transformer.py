"""BERT-style transformer encoder — the reference's BERT/PowerSGD config.

BASELINE.json lists "BERT + PowerSGD rank-4" among the configs to support;
the reference itself defers BERT to the external grace-benchmarks repo
(README.md:34). grace-tpu ships a functional encoder: LayerNorm-only (so the
model is stateless — no BN running stats), bf16-friendly, MXU-shaped matmuls.
PowerSGD on its 2-D weight matrices is the intended pairing.

Masked-LM head included so examples can train on real objectives; the bench
path uses sequence classification over pooled [CLS].

An encoder with learned positions, LayerNorm and GELU; the causal decoder
(RMSNorm, rotary positions, gated feed-forward, experts, next-token loss) is
``models/lfm2.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from grace_tpu.models import layers as L


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 30522
    d_model: int = 768
    num_heads: int = 12
    num_layers: int = 12
    d_ff: int = 3072
    max_len: int = 512
    num_classes: int = 2


def base(**kw) -> Config:
    return Config(**kw)


def tiny(**kw) -> Config:
    """Test-scale config."""
    d = dict(vocab_size=1000, d_model=64, num_heads=4, num_layers=2,
             d_ff=128, max_len=64, num_classes=2)
    d.update(kw)
    return Config(**d)


def _layer_init(key, cfg: Config):
    k = L.split_keys(key, 6)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln1": L.ln_init(d),
        "qkv": L.dense_init(k[0], d, 3 * d, init="trunc"),
        "proj": L.dense_init(k[1], d, d, init="trunc"),
        "ln2": L.ln_init(d),
        "ff1": L.dense_init(k[2], d, f, init="trunc"),
        "ff2": L.dense_init(k[3], f, d, init="trunc"),
    }


def _attention(p, x, mask, num_heads):
    """Pre-LN multi-head self-attention. x: (N, T, D)."""
    n, t, d = x.shape
    h = num_heads
    dh = d // h
    qkv = L.dense_apply(p["qkv"], x).reshape(n, t, 3, h, dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (N, T, H, Dh)
    logits = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(dh).astype(x.dtype)
    if mask is not None:
        big_neg = jnp.asarray(-1e9, logits.dtype)
        logits = jnp.where(mask[:, None, None, :], logits, big_neg)
    attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("nhqk,nkhd->nqhd", attn, v).reshape(n, t, d)
    return L.dense_apply(p["proj"], out)


def _layer_apply(p, x, mask, cfg: Config):
    y = L.ln_apply(p["ln1"], x)
    x = x + _attention(p, y, mask, cfg.num_heads)
    y = L.ln_apply(p["ln2"], x)
    y = L.dense_apply(p["ff2"], jax.nn.gelu(L.dense_apply(p["ff1"], y)))
    return x + y


def init(key: jax.Array, cfg: Config) -> Tuple[L.Params, L.ModelState]:
    k = L.split_keys(key, 4 + cfg.num_layers)
    params = {
        "tok_emb": L.embedding_init(k[0], cfg.vocab_size, cfg.d_model),
        "pos_emb": L.embedding_init(k[1], cfg.max_len, cfg.d_model),
        "ln_f": L.ln_init(cfg.d_model),
        "cls": L.dense_init(k[2], cfg.d_model, cfg.num_classes, init="trunc"),
        "layers": [_layer_init(k[4 + i], cfg) for i in range(cfg.num_layers)],
    }
    return params, {}


def encode(params: L.Params, ids: jax.Array, cfg: Config,
           mask: Optional[jax.Array] = None,
           dtype=jnp.float32) -> jax.Array:
    """ids: (N, T) int32 → hidden states (N, T, D)."""
    t = ids.shape[1]
    if t > cfg.max_len:
        raise ValueError(f"sequence length {t} exceeds max_len {cfg.max_len}")
    x = L.embedding_apply(params["tok_emb"], ids, dtype=dtype)
    x = x + L.embedding_apply(params["pos_emb"], jnp.arange(t), dtype=dtype)
    for lp in params["layers"]:
        x = _layer_apply(lp, x, mask, cfg)
    return L.ln_apply(params["ln_f"], x)


def apply(params: L.Params, state: L.ModelState, ids: jax.Array, *,
          cfg: Config, mask: Optional[jax.Array] = None, train: bool = True,
          dtype=jnp.float32) -> Tuple[jax.Array, L.ModelState]:
    """Sequence classification over the first token → logits (N, C)."""
    del train
    x = encode(params, ids, cfg, mask, dtype)
    pooled = x[:, 0].astype(jnp.float32)
    return L.dense_apply(params["cls"], pooled), state


def mlm_logits(params: L.Params, ids: jax.Array, cfg: Config,
               mask: Optional[jax.Array] = None,
               dtype=jnp.float32) -> jax.Array:
    """Masked-LM logits via weight tying with the token embedding."""
    x = encode(params, ids, cfg, mask, dtype)
    return x.astype(jnp.float32) @ params["tok_emb"]["table"].T
