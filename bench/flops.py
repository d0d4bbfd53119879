"""Model FLOPs and bytes of a decoder step, from a configuration's shapes.

The yardstick's own count, independent of the program's cost model. A
processed token costs 2 FLOPs per weight of every layer matmul (q, k, v, o,
gate, up, down) plus causal attention over its real context: at position p
(0-based) it attends to p + 1 keys, at 2 FLOPs per multiply-add for q.k and
again for p.v, in every query head. Each sampled token also costs one row of
the unembedding, 2 * d_model * vocab. Padding is never counted.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class FlopModel:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "FlopModel":
        m = cfg["model"]
        return cls(layers=m["num_layers"], d_model=m["d_model"],
                   heads=m["num_heads"], kv_heads=m["num_kv_heads"],
                   head_dim=m["head_dim"], d_ff=m["d_ff"],
                   vocab=m["vocab_size"], tied=bool(m.get("tie_embeddings")))

    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * d * self.d_ff

    def matmul_params(self) -> int:
        """Weights of every matmul of a token's pass, unembedding included."""
        return self.layers * self.layer_matmul_params() + \
            self.d_model * self.vocab

    def param_count(self) -> int:
        """All served weights: matmuls, embedding (if untied) and norms."""
        n = self.matmul_params() + self.layers * 2 * self.d_model + \
            self.d_model
        return n if self.tied else n + self.vocab * self.d_model

    def token_flops(self, lo: int, hi: int) -> float:
        """FLOPs of computing positions [lo, hi) of one sequence."""
        n = hi - lo
        ctx = (hi * (hi + 1) - lo * (lo + 1)) // 2      # sum of p + 1
        attn = 4.0 * self.layers * self.heads * self.head_dim * ctx
        return 2.0 * self.layers * self.layer_matmul_params() * n + attn

    def logits_flops(self) -> float:
        return 2.0 * self.d_model * self.vocab

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * itemsize

    def weight_bytes(self) -> int:
        """Bytes the device reads for the weights once per step: bf16
        matmul weights and embedding, fp32 norms."""
        norms = (self.layers * 2 + 1) * self.d_model
        return 2 * (self.param_count() - norms) + 4 * norms

    def step_bytes(self, context_tokens: int, new_tokens: int) -> int:
        """Least HBM traffic of one step: the weights once, every cached
        key and value it attends over once, and the new ones written."""
        kv = self.kv_bytes_per_token()
        return self.weight_bytes() + kv * (context_tokens + new_tokens)
