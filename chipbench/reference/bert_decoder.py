"""Plain float32 reference of a BERT-shaped causal decoder: the encoder of
Devlin et al. 2018 ("BERT", section 3 and its released ``modeling.py``) with
the attention mask made causal, as HF ``BertLMHeadModel(is_decoder=True)``
runs an encoder checkpoint's shape as a generator.

Full forward over the whole sequence, no cache, no paging, no batching
tricks; ``jax.numpy`` only. It imports nothing of ``deeplearning4j_tpu`` and
makes its own weights from the seed.

Layout, as published: word + learned position + token-type-0 embeddings,
LayerNorm (eps 1e-12); per layer ``h = LN(x + MHA(x))``, ``out = LN(h +
FFN(h))`` (post-LN), GELU by erf, attention scaled by 1/sqrt(head size).
Departure: the output head is an untied ``hidden x vocab`` matrix with a bias
(the program's ``RnnOutputLayer``); BERT's MLM head ties it to the word
embeddings behind a transform layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-12
INIT_STD = 0.02
_LAYER_MATS = (("Wq", "h", "h"), ("Wk", "h", "h"), ("Wv", "h", "h"),
               ("Wo", "h", "h"), ("W1", "h", "f"), ("W2", "f", "h"))
_LAYER_VECS = (("bq", "h"), ("bk", "h"), ("bv", "h"), ("bo", "h"),
               ("b1", "f"), ("b2", "h"), ("ln1_b", "h"), ("ln2_b", "h"))


def make_weights(seed: int, cfg: dict):
    """Every matrix, bias and LayerNorm shift N(0, 0.02); LayerNorm scales
    1 + N(0, 0.02): nothing is left at a value (0 or 1) that would hide a
    term the program dropped. One jitted call on the device, float32."""
    dims = {"h": cfg["hidden_size"], "f": cfg["intermediate_size"]}
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    n_pos, n_type = cfg["max_position_embeddings"], cfg["type_vocab_size"]

    @jax.jit
    def build(key):
        def normal(k, shape, mean=0.0):
            return mean + INIT_STD * jax.random.normal(k, shape, jnp.float32)

        k_emb, k_head, k_layers = jax.random.split(key, 3)
        ke = jax.random.split(k_emb, 5)
        h = dims["h"]
        w = {"emb": {"word": normal(ke[0], (vocab, h)),
                     "pos": normal(ke[1], (n_pos, h)),
                     "type": normal(ke[2], (n_type, h)),
                     "gamma": normal(ke[3], (h,), 1.0),
                     "beta": normal(ke[4], (h,))}}
        layers = []
        for kl in jax.random.split(k_layers, n_layers):
            ks = iter(jax.random.split(kl, 16))
            lyr = {n: normal(next(ks), (dims[a], dims[b]))
                   for n, a, b in _LAYER_MATS}
            lyr.update({n: normal(next(ks), (dims[a],))
                        for n, a in _LAYER_VECS})
            lyr["ln1_g"] = normal(next(ks), (h,), 1.0)
            lyr["ln2_g"] = normal(next(ks), (h,), 1.0)
            layers.append(lyr)
        w["layers"] = layers
        kh = jax.random.split(k_head, 2)
        w["head"] = {"W": normal(kh[0], (h, vocab)),
                     "b": normal(kh[1], (vocab,))}
        return w

    return build(jax.random.PRNGKey(seed % (2 ** 31)))


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def hidden(w, tokens, n_heads: int, dtype=jnp.float32):
    """tokens (B, T) int32 -> final hidden states (B, T, H), causal."""
    mm = functools.partial(jnp.matmul, precision="highest")
    cast = lambda a: a.astype(dtype)
    e = w["emb"]
    t = tokens.shape[1]
    x = (cast(e["word"])[tokens] + cast(e["pos"])[None, :t]
         + cast(e["type"])[0])
    x = _ln(x, cast(e["gamma"]), cast(e["beta"]))
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lyr in w["layers"]:
        p = {k: cast(v) for k, v in lyr.items()}
        b, _, hs = x.shape
        dh = hs // n_heads
        split = lambda y: y.reshape(b, t, n_heads, dh).transpose(0, 2, 1, 3)
        q = split(mm(x, p["Wq"]) + p["bq"])
        k = split(mm(x, p["Wk"]) + p["bk"])
        v = split(mm(x, p["Wv"]) + p["bv"])
        s = mm(q, k.transpose(0, 1, 3, 2)) / (dh ** 0.5)
        s = jnp.where(causal, s, -jnp.inf)
        a = mm(jax.nn.softmax(s, axis=-1), v)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, hs)
        h = _ln(x + mm(a, p["Wo"]) + p["bo"], p["ln1_g"], p["ln1_b"])
        f = jax.nn.gelu(mm(h, p["W1"]) + p["b1"], approximate=False)
        x = _ln(h + mm(f, p["W2"]) + p["b2"], p["ln2_g"], p["ln2_b"])
    return x


@functools.partial(jax.jit, static_argnames=("n_heads", "dtype"))
def logits_at(w, tokens, positions, n_heads: int, dtype=jnp.float32):
    """Next-token logits (B, P, V) at ``positions`` (B, P) of ``tokens``
    (B, T): row b's entry p is the distribution over the token that follows
    position ``positions[b, p]``. ``dtype=bfloat16`` is the control: weights,
    activations, LayerNorm and softmax all in bfloat16."""
    x = hidden(w, tokens, n_heads, dtype)
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    out = jnp.matmul(xs, w["head"]["W"].astype(dtype), precision="highest")
    return (out + w["head"]["b"].astype(dtype)).astype(jnp.float32)
