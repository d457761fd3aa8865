"""The work a step needs, from the configuration's published shapes alone.

These counts are the yardstick of the roofline and MFU metrics. They use the
sizes in ``configs/<config>.json`` and the live lengths of the slots, never
the compiled program: a kernel that does less work than the program does
today raises its share of the roofline, and cannot make the count stale.

Counted: every weight matrix once per step (a decode reads each weight once,
whatever the batch), the keys and values a step must read at each slot's
live length and write for its new tokens, and two operations per
multiply-add. Left out, as small beside these: norms, RoPE, softmax,
biases, the token ids and the logits moved to the host.
"""
from __future__ import annotations

import dataclasses

BYTES_BF16 = 2


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool
    tied_embeddings: bool
    qk_norm: bool
    attn_bias: bool      # biases on q, k, v
    out_bias: bool       # bias on the attention output and the MLP
    layernorm: bool      # LayerNorm (scale and bias) instead of RMSNorm

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated_mlp=c["mlp_gated"],
                   tied_embeddings=c["tie_word_embeddings"],
                   qk_norm=c["qk_norm"], attn_bias=c["qkv_bias"],
                   out_bias=c["out_bias"], layernorm=c["norm"] == "layernorm")

    # ------------------------------------------------------------ params
    def layer_matmul_params(self) -> int:
        D, H, KV, hd, F = (self.d_model, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        mlp = (3 if self.gated_mlp else 2) * D * F
        return attn + mlp

    def layer_vector_params(self) -> int:
        D, H, KV, hd, F = (self.d_model, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        n = 2 * D * (2 if self.layernorm else 1)
        if self.qk_norm:
            n += 2 * hd
        if self.attn_bias:
            n += H * hd + 2 * KV * hd
        if self.out_bias:
            n += D + F + D
        return n

    def param_count(self) -> int:
        """Parameters of the published model (vocabulary unpadded)."""
        emb = self.vocab * self.d_model
        head = 0 if self.tied_embeddings else self.vocab * self.d_model
        final_norm = self.d_model * (2 if self.layernorm else 1)
        return (emb + head + final_norm + self.layers *
                (self.layer_matmul_params() + self.layer_vector_params()))

    def weight_bytes(self) -> int:
        return BYTES_BF16 * self.param_count()

    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * BYTES_BF16

    # ------------------------------------------------------------ FLOPs
    def _token_matmul_flops(self) -> int:
        """Per token: every layer's matrices (the embedding lookup is not
        a matmul)."""
        return 2 * self.layers * self.layer_matmul_params()

    def _head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def _attn_flops(self, pairs: int) -> int:
        """QK^T and PV over ``pairs`` (query, key) pairs in every layer."""
        return 4 * self.layers * self.heads * self.head_dim * pairs

    def decode_flops(self, positions) -> int:
        """One decode iteration: one new token in each live slot, whose
        cache holds ``p`` earlier positions; it attends to ``p + 1``."""
        n = len(positions)
        pairs = sum(p + 1 for p in positions)
        return (n * (self._token_matmul_flops() + self._head_flops())
                + self._attn_flops(pairs))

    def decode_bytes(self, positions) -> int:
        """Weights once, each slot's live keys and values read, and one new
        key and value written per slot."""
        kv = self.kv_bytes_per_token()
        return (self.weight_bytes() + kv * sum(positions)
                + kv * len(positions))

    def prefill_flops(self, length: int) -> int:
        """A prompt of ``length`` tokens, causal, with logits for the last
        position only (the one the first token is taken from)."""
        pairs = length * (length + 1) // 2
        return (length * self._token_matmul_flops() + self._head_flops()
                + self._attn_flops(pairs))


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time at peak."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
