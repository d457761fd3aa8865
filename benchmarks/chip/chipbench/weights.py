"""Random weights from a seed, made on the device in one program.

The tree has the layout the serving engine takes (stacked layers, matrices
stored input-major, the embedding padded to the engine's vocabulary rows),
and every leaf is drawn in the served dtype, so no float32 copy of the model
is ever held. The benchmark makes these weights and hands the same arrays
to the program and to the reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1
BIAS_STD = 0.1


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (the run's seed may exceed 32
    bits) and a stream number."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def layout(cfg: dict, vocab_rows: int) -> dict:
    """{path: (shape, kind)} for every leaf. kind: matrix fan-in | 'embed' |
    'scale' | 'bias'."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    F = cfg["intermediate_size"]
    ln = cfg["norm"] == "layernorm"

    def norm(prefix, pre):
        d = {prefix + ("scale",): (pre + (D,), "scale")}
        if ln:
            d[prefix + ("bias",)] = (pre + (D,), "bias")
        return d

    leaves = {("embed", "table"): ((vocab_rows, D), "embed")}
    leaves.update(norm(("final_norm",), ()))
    if not cfg["tie_word_embeddings"]:
        leaves[("lm_head", "w")] = ((D, vocab_rows), D)
    lay = ("layers",)
    leaves.update(norm(lay + ("ln1",), (L,)))
    leaves.update(norm(lay + ("ln2",), (L,)))
    a = lay + ("attn",)
    leaves[a + ("wq",)] = ((L, D, H * hd), D)
    leaves[a + ("wk",)] = ((L, D, KV * hd), D)
    leaves[a + ("wv",)] = ((L, D, KV * hd), D)
    leaves[a + ("wo",)] = ((L, H * hd, D), H * hd)
    if cfg["qkv_bias"]:
        leaves[a + ("bq",)] = ((L, H * hd), "bias")
        leaves[a + ("bk",)] = ((L, KV * hd), "bias")
        leaves[a + ("bv",)] = ((L, KV * hd), "bias")
    if cfg["qk_norm"]:
        leaves[a + ("q_norm",)] = ((L, hd), "scale")
        leaves[a + ("k_norm",)] = ((L, hd), "scale")
    if cfg["out_bias"]:
        leaves[a + ("bo",)] = ((L, D), "bias")
    m = lay + ("mlp",)
    leaves[m + ("wi",)] = ((L, D, F), D)
    if cfg["mlp_gated"]:
        leaves[m + ("wg",)] = ((L, D, F), D)
    leaves[m + ("wo",)] = ((L, F, D), F)
    if cfg["out_bias"]:
        leaves[m + ("bi",)] = ((L, F), "bias")
        leaves[m + ("bo",)] = ((L, D), "bias")
    return leaves


def _draw(key, shape, kind, dtype, vocab):
    if kind == "embed":
        w = jax.random.normal(key, shape, dtype) * jnp.asarray(EMBED_STD, dtype)
        rows = jnp.arange(shape[0])[:, None] < vocab
        return jnp.where(rows, w, jnp.zeros((), dtype))  # padding rows: 0
    if kind == "scale":
        return (1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if kind == "bias":
        return jax.random.normal(key, shape, dtype) * jnp.asarray(BIAS_STD,
                                                                  dtype)
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        1.0 / math.sqrt(kind), dtype)


def make_weights(cfg: dict, seed: int, vocab_rows: int,
                 dtype=jnp.bfloat16) -> dict:
    """The whole tree, drawn on the default device by one jitted call."""
    leaves = layout(cfg, vocab_rows)
    paths = sorted(leaves)
    vocab = cfg["vocab_size"]

    def build(key):
        keys = jax.random.split(key, len(paths))
        tree: dict = {}
        for k, path in zip(keys, paths):
            shape, kind = leaves[path]
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = _draw(k, shape, kind, dtype, vocab)
        return tree

    return jax.block_until_ready(jax.jit(build)(key_from_seed(seed, 0)))
