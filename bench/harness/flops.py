"""Operations and bytes of the model's layers, computed from the shapes
in a configuration file.  These are the numerators of ``mfu`` and of
the roofline shares: the work the layer asks for, whichever kernel
does it, so a later change of kernel is measured against the same work.

Conventions (one multiply-add is 2 FLOPs):

- a decoder layer's matmuls: q, k, v and o projections and a gated MLP
  (gate, up, down);
- attention of one query at absolute position ``p`` reads the keys and
  values of positions ``0..p``: ``4 * heads * head_dim * (p + 1)``
  FLOPs per layer (scores and weighted sum);
- the least bytes of a matmul dispatch: every weight code once, plus
  each activation in and out once at the configuration's activation
  width; logits leave in float32;
- the least bytes of attention: the keys and values of the live
  context once, the new token's keys and values written once, the
  query in and the context out at the activation width.
"""

from __future__ import annotations

import dataclasses

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "float8_e4m3fn": 1,
          "float8_e5m2": 1, "int8": 1, "exp7": 1}


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    w_bytes: int
    act_bytes: int
    kv_bytes: int

    @classmethod
    def of(cls, config: dict) -> "Shapes":
        d = int(config["hidden_size"])
        heads = int(config["num_attention_heads"])
        rep = config["representation"]
        return cls(d=d, ff=int(config["intermediate_size"]),
                   layers=int(config["num_hidden_layers"]), heads=heads,
                   kv_heads=int(config["num_key_value_heads"]),
                   head_dim=int(config.get("head_dim") or d // heads),
                   vocab=int(config["vocab_size"]),
                   w_bytes=_BYTES[rep["weights"]],
                   act_bytes=_BYTES[rep["activations"]],
                   kv_bytes=_BYTES[rep["kv"]])


def layer_matmul_params(s: Shapes) -> int:
    """Weights one token meets in one layer's matmuls."""
    qo = 2 * s.d * s.heads * s.head_dim
    kv = 2 * s.d * s.kv_heads * s.head_dim
    return qo + kv + 3 * s.d * s.ff


def matmul_flops(s: Shapes, rows: int, unembed_rows: int) -> float:
    """``rows`` tokens through every layer's matmuls, plus
    ``unembed_rows`` of them through the output projection."""
    return (2.0 * rows * s.layers * layer_matmul_params(s)
            + 2.0 * unembed_rows * s.d * s.vocab)


def matmul_bytes(s: Shapes, rows: int, unembed_rows: int) -> float:
    hd, a = s.head_dim, s.act_bytes
    per_row = (s.d + (s.heads + 2 * s.kv_heads) * hd      # attn in, qkv out
               + s.heads * hd + s.d                       # o in, o out
               + s.d + s.ff                               # mlp in, gated out
               + s.ff + s.d)                              # down in, out
    weights = (s.layers * layer_matmul_params(s) + s.vocab * s.d) * s.w_bytes
    return (weights + rows * s.layers * per_row * a
            + unembed_rows * (s.d * a + s.vocab * 4))


def attn_flops(s: Shapes, q_start: int, q_len: int) -> float:
    """Queries at positions ``q_start .. q_start + q_len - 1``."""
    ctx_sum = q_len * (q_start + 1) + q_len * (q_len - 1) / 2
    return s.layers * 4.0 * s.heads * s.head_dim * ctx_sum


def attn_bytes(s: Shapes, q_start: int, q_len: int) -> float:
    kv_tok = s.kv_heads * s.head_dim * 2 * s.kv_bytes
    act = q_len * s.heads * s.head_dim * 2 * s.act_bytes
    return s.layers * ((q_start + q_len) * kv_tok + q_len * kv_tok + act)


def token_flops(s: Shapes, position: int, unembed: bool) -> float:
    """One token's forward at absolute ``position``: the useful work
    ``mfu`` counts (no padding)."""
    return (matmul_flops(s, 1, int(unembed))
            + attn_flops(s, position, 1))


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes_s: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and
    which of the two bounds it."""
    tc, tm = flops / peak_flops, nbytes / peak_bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
