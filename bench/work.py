"""Operations and bytes that the algorithm needs, counted from shapes.

Each count is of the work itself, whatever implements it, so that a
kernel can approach its roofline and never pass it: packed weight words
at the configuration's storage bits plus their scales, activations read
and written once in the activation dtype, attention over the live
context lengths (not the cache capacity), and for the equalizer each W
once per subcarrier and each y and estimate once per (subcarrier,
symbol), in float32.  A "call" is one kernel launch: (flops, bytes).
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

Call = Tuple[float, float]     # (flops, bytes) of one kernel launch

SCALE_BYTES = 4                # one float32 scale per weight tensor / row


@dataclasses.dataclass(frozen=True)
class DenseLM:
    """The sizes of a dense decoder LM, named as in its published config."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    weight_bits: int       # packed weight word
    kv_bits: int           # packed KV-cache word
    act_bytes: int = 2     # bf16 activations

    @classmethod
    def from_config(cls, cfg: dict, weight_bits: int, kv_bits: int):
        return cls(layers=cfg["num_hidden_layers"], hidden=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], ffn=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"], weight_bits=weight_bits,
                   kv_bits=kv_bits)

    def layer_matmuls(self) -> List[Tuple[int, int]]:
        """(d_in, d_out) of each weight matmul of one layer: q, k, v, o,
        gate, up, down."""
        d, q, kv, f = (self.hidden, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.ffn)
        return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]

    def layer_params(self) -> int:
        return sum(k * n for k, n in self.layer_matmuls())

    def matmul_params(self) -> int:
        """Every weight that a matmul reads for a token whose logits are
        needed: the layers and the LM head (the embedding is a gather)."""
        return self.layers * self.layer_params() + self.hidden * self.vocab


def matmul_call(m: int, k: int, n: int, weight_bits: int,
                act_bytes: int) -> Call:
    flops = 2.0 * m * k * n
    nbytes = k * n * weight_bits / 8 + SCALE_BYTES + (m * k + m * n) \
        * act_bytes
    return flops, nbytes


def dequant_matmul_calls(lm: DenseLM, rows: int, head_rows: int
                         ) -> List[Call]:
    """The `vp_dequant_matmul` launches of one model step over `rows`
    token rows, of which `head_rows` need logits."""
    calls = []
    for _ in range(lm.layers):
        calls += [matmul_call(rows, k, n, lm.weight_bits, lm.act_bytes)
                  for k, n in lm.layer_matmuls()]
    calls.append(matmul_call(head_rows, lm.hidden, lm.vocab, lm.weight_bits,
                             lm.act_bytes))
    return calls


def decode_attention_calls(lm: DenseLM, lengths: Sequence[int]
                           ) -> List[Call]:
    """The `vp_decode_attention` launches of one decode step, one per
    layer, over the live context length of each active row (the new
    token included)."""
    live = sum(lengths)
    hd = lm.heads * lm.head_dim
    flops = 4.0 * live * hd                      # q.k and p.v
    nbytes = (2 * live * lm.kv_heads * lm.head_dim * lm.kv_bits / 8
              + 2 * live * SCALE_BYTES
              + 2 * len(lengths) * hd * lm.act_bytes)   # q in, out
    return [(flops, nbytes)] * lm.layers


def attention_flops(lm: DenseLM, start: int, count: int) -> float:
    """Causal attention FLOPs of `count` tokens at positions start ..
    start + count - 1, each attending to itself and all before it."""
    # sum over p = start+1 .. start+count of 4 * p * heads * head_dim
    spans = count * start + count * (count + 1) / 2
    return 4.0 * spans * lm.heads * lm.head_dim * lm.layers


def model_flops(lm: DenseLM, start: int, count: int, logits: int) -> float:
    """Model FLOPs of `count` tokens after `start` cached positions, of
    which `logits` need the LM head: 2 x matmul parameters per token,
    plus causal attention at the real context lengths."""
    return (2.0 * lm.layers * lm.layer_params() * count
            + 2.0 * lm.hidden * lm.vocab * logits
            + attention_flops(lm, start, count))


def roofline_seconds(calls: Sequence[Call], peak_flops: float,
                     peak_bytes_s: float) -> Tuple[float, float, float]:
    """(least seconds, of which compute-bound, of which bytes-bound):
    each launch takes at least the larger of its two bounds."""
    total = by_flops = by_bytes = 0.0
    for flops, nbytes in calls:
        tf, tb = flops / peak_flops, nbytes / peak_bytes_s
        total += max(tf, tb)
        if tf >= tb:
            by_flops += tf
        else:
            by_bytes += tb
    return total, by_flops, by_bytes


def equalizer_slot(subcarriers: int, symbols: int, users: int,
                   antennas: int) -> Call:
    """One slot: one (U, B) W per subcarrier applied to `symbols`
    received vectors; complex, as 4 real products, in float32."""
    n = subcarriers * symbols
    flops = 8.0 * n * users * antennas
    nbytes = 4 * 2 * (subcarriers * users * antennas   # W
                      + n * antennas                   # y
                      + n * users)                     # estimates
    return flops, nbytes
