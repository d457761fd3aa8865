"""Plain float32 reference of the dense decoder LMs the benchmark serves.

Written from the published architectures (Hugging Face ``modeling_qwen3``
and ``modeling_starcoder2``), in straightforward ``jax.numpy`` at
``default_matmul_precision("highest")``, one sequence at a time and one
layer at a time, with no cache and no batching. It imports nothing of the
program under test; it reads the configuration file and the weights the
benchmark made.

What the configuration's flags select:

- ``norm``: ``rmsnorm`` (x / rms(x) * scale) or ``layernorm`` (mean and
  variance, scale and bias), with ``norm_eps``;
- ``qk_norm``: RMSNorm over each head of q and k before RoPE (Qwen3);
- ``qkv_bias``: biases on the q, k and v projections; ``out_bias``: biases
  on the attention output and both MLP matrices (StarCoder2's use_bias);
- ``mlp_gated``: down(act(gate(x)) * up(x)) with SiLU, else
  c_proj(act(c_fc(x))) with tanh-approximated GELU;
- ``attention_window``: keys further back than this are masked (0: none).

RoPE rotates halves (``rotate_half``), GQA query head h reads key/value
head h // (heads / kv_heads), scores are scaled by head_dim ** -0.5, and the
head is the embedding (tied) over the published vocabulary only.

``int8=True`` is the lower-precision control: every linear layer's weight
is rounded to int8 per output channel and its input to int8 per token
(symmetric, absmax), the step a serving stack takes below bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 1024     # sequences are padded to a multiple of this, and the
ROW_BUCKET = 256  # positions read to a multiple of this: few compiles


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (absmax scale), in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


class Reference:
    def __init__(self, cfg: dict, *, int8: bool = False):
        self.cfg = cfg
        self.int8 = int8
        self._layer = jax.jit(self._layer_fn)
        self._embed = jax.jit(self._embed_fn)
        self._head = jax.jit(self._head_fn)

    # ------------------------------------------------------------ parts
    def _linear(self, x, w, b=None):
        w = w.astype(jnp.float32)
        if self.int8:
            x = _q8(x, -1)
            w = _q8(w, 0)
        y = x @ w
        return y if b is None else y + b.astype(jnp.float32)

    def _norm(self, x, p):
        eps = self.cfg["norm_eps"]
        scale = p["scale"].astype(jnp.float32)
        if self.cfg["norm"] == "layernorm":
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
            return (x - mu) / jnp.sqrt(var + eps) * scale + \
                p["bias"].astype(jnp.float32)
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
            * scale

    def _rope(self, x, pos):
        """x: (S, heads, hd). rotate_half convention."""
        hd = x.shape[-1]
        inv = 1.0 / (float(self.cfg["rope_theta"]) **
                     (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        ang = jnp.concatenate([ang, ang], -1)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        rot = jnp.concatenate([-x2, x1], -1)
        return x * jnp.cos(ang) + rot * jnp.sin(ang)

    def _attention(self, h, p):
        c = self.cfg
        S = h.shape[0]
        H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
        q = self._linear(h, p["wq"], p.get("bq")).reshape(S, H, hd)
        k = self._linear(h, p["wk"], p.get("bk")).reshape(S, KV, hd)
        v = self._linear(h, p["wv"], p.get("bv")).reshape(S, KV, hd)
        if c["qk_norm"]:
            eps = c["norm_eps"]
            q = q / jnp.sqrt(jnp.mean(q * q, -1, keepdims=True) + eps) * \
                p["q_norm"].astype(jnp.float32)
            k = k / jnp.sqrt(jnp.mean(k * k, -1, keepdims=True) + eps) * \
                p["k_norm"].astype(jnp.float32)
        pos = jnp.arange(S)
        q, k = self._rope(q, pos), self._rope(k, pos)
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)  # head h reads kv head h // rep
        v = jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        qi, ki = pos[:, None], pos[None, :]
        mask = ki <= qi
        if c["attention_window"]:
            mask = mask & (qi - ki < c["attention_window"])
        s = jnp.where(mask[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(S, H * hd)
        return self._linear(o, p["wo"], p.get("bo"))

    def _mlp(self, h, p):
        if self.cfg["mlp_gated"]:
            g = jax.nn.silu(self._linear(h, p["wg"]))
            return self._linear(g * self._linear(h, p["wi"]), p["wo"])
        a = jax.nn.gelu(self._linear(h, p["wi"], p.get("bi")),
                        approximate=True)
        return self._linear(a, p["wo"], p.get("bo"))

    # ------------------------------------------------------------ programs
    def _embed_fn(self, table, tokens):
        return table[tokens].astype(jnp.float32)

    def _layer_fn(self, layers, i, x):
        with jax.default_matmul_precision("highest"):
            p = jax.tree_util.tree_map(lambda a: a[i], layers)
            x = x + self._attention(self._norm(x, p["ln1"]), p["attn"])
            return x + self._mlp(self._norm(x, p["ln2"]), p["mlp"])

    def _head_fn(self, weights, x, rows):
        with jax.default_matmul_precision("highest"):
            h = self._norm(x[rows], weights["final_norm"])
            V = self.cfg["vocab_size"]
            if self.cfg["tie_word_embeddings"]:
                w = weights["embed"]["table"][:V].astype(jnp.float32).T
            else:
                w = weights["lm_head"]["w"][:, :V].astype(jnp.float32)
            return self._linear(h, w)

    def logits(self, weights, tokens, rows) -> jax.Array:
        """float32 logits (len(rows), vocab) at positions ``rows`` of the
        sequence ``tokens``; position i predicts token i + 1."""
        n = len(tokens)
        padded = -(-n // BUCKET) * BUCKET
        t = np.zeros(padded, np.int32)
        t[:n] = tokens  # causal: padding after n never reaches rows < n
        x = self._embed(weights["embed"]["table"], jnp.asarray(t))
        layers = weights["layers"]
        for i in range(self.cfg["num_hidden_layers"]):
            x = self._layer(layers, jnp.int32(i), x)
        m = len(rows)
        r = np.full(-(-m // ROW_BUCKET) * ROW_BUCKET, rows[-1], np.int32)
        r[:m] = rows
        return self._head(weights, x, jnp.asarray(r))[:m]


def served_gaps(ref_logits, served) -> np.ndarray:
    """Per position: the reference's best logit minus its logit of the
    served token (inf where the token is outside the vocabulary)."""
    served = np.asarray(served)
    lg = np.asarray(ref_logits)
    best = lg.max(-1)
    inside = (served >= 0) & (served < lg.shape[-1])
    got = lg[np.arange(len(served)), np.where(inside, served, 0)]
    return np.where(inside, best - got, np.inf)


def control_gaps(ref_logits, ctl_logits) -> np.ndarray:
    """Per position: the gap of the token the control puts first."""
    return served_gaps(ref_logits, np.asarray(ctl_logits).argmax(-1))
