"""Plain reference of the encoder the ``bert-base-squad`` configuration
trains, in ``jax.numpy``: weights from a seed, forward pass, SQuAD span
loss. Imports nothing of ``grace_tpu``.

Sizes are BERT-Base's (google-research/bert ``bert_config.json``: 12
layers, hidden 768, 12 heads, intermediate 3072, vocabulary 30522) and the
job is ``run_squad.py``'s (sequence 384, a start and an end logit per
position, the mean of the two cross-entropies... here their sum, as the
program's loss has it). Departures from the published model, the same the
program's makes: layer norm before each sub-layer and once at the end
(pre-LN) instead of after, no segment embedding and no embedding layer
norm, query/key/value as one fused projection, tanh-approximated GELU, no
dropout. Precision is the configuration's: parameters ``param_dtype``,
activations ``activation_dtype``, layer norm, softmax and the span head in
float32.

The weights are laid out as the nested dict the program's model reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-6
INIT_STD = 0.02


def _trunc(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * INIT_STD).astype(dtype)


def init(key, sizes, param_dtype=jnp.float32):
    """Seeded weights: ``(params, {})`` (the model has no other state)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    n = [0]

    def k():
        n[0] += 1
        return jax.random.fold_in(key, n[0])

    def dense(din, dout):
        return {"w": _trunc(k(), (din, dout), param_dtype),
                "b": jnp.zeros((dout,), param_dtype)}

    def ln():
        return {"scale": jnp.ones((d,), param_dtype),
                "bias": jnp.zeros((d,), param_dtype)}

    params = {
        "tok_emb": {"table": _trunc(k(), (sizes["vocab_size"], d),
                                    param_dtype)},
        "pos_emb": {"table": _trunc(k(), (sizes["max_seq_length"], d),
                                    param_dtype)},
        "ln_f": ln(),
        "cls": dense(d, 2),
        "layers": [{"ln1": ln(), "qkv": dense(d, 3 * d),
                    "proj": dense(d, d), "ln2": ln(),
                    "ff1": dense(d, f), "ff2": dense(f, d)}
                   for _ in range(sizes["num_hidden_layers"])],
    }
    return params, {}


def make_batch(key, n, sizes):
    """``n`` token rows with a start and an end position each, every row
    different."""
    seq = sizes["max_seq_length"]
    ki, ka, kb = jax.random.split(key, 3)
    ids = jax.random.randint(ki, (n, seq), 0, sizes["vocab_size"], jnp.int32)
    spans = jnp.stack(
        [jax.random.randint(ka, (n,), 0, seq // 2, jnp.int32),
         jax.random.randint(kb, (n,), seq // 2, seq, jnp.int32)], 1)
    return ids, spans


def _dense(p, x):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _ln(p, x):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _attention(p, x, heads):
    n, t, d = x.shape
    dh = d // heads
    qkv = _dense(p["qkv"], x).reshape(n, t, 3, heads, dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(dh).astype(x.dtype)
    attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("nhqk,nkhd->nqhd", attn, v).reshape(n, t, d)
    return _dense(p["proj"], out)


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


def loss(params, state, batch, sizes, activation_dtype=jnp.bfloat16):
    """Start-position plus end-position cross-entropy, mean over the batch:
    ``(loss, state)``."""
    ids, spans = batch
    t = ids.shape[1]
    x = jnp.take(params["tok_emb"]["table"].astype(activation_dtype), ids,
                 axis=0)
    x = x + jnp.take(params["pos_emb"]["table"].astype(activation_dtype),
                     jnp.arange(t), axis=0)
    for p in params["layers"]:
        x = x + _attention(p, _ln(p["ln1"], x), sizes["num_attention_heads"])
        h = jax.nn.gelu(_dense(p["ff1"], _ln(p["ln2"], x)))
        x = x + _dense(p["ff2"], h)
    x = _ln(params["ln_f"], x).astype(jnp.float32)
    cls = params["cls"]
    logits = x @ cls["w"].astype(jnp.float32) + cls["b"].astype(jnp.float32)
    total = _xent(logits[..., 0], spans[:, 0]) + _xent(logits[..., 1],
                                                       spans[:, 1])
    return jnp.mean(total), state
