"""Peaks of each chip, and the operations and bytes the algorithm needs.

The counts are functions of shapes and of the state the work touches, never
of how the program implements it: a decode step needs every weight once,
the keys and values of the blocks inside the live contexts (a block that
several rows share is read once), and the new token's key and value write.
A program that gathers a wider window, or copies its pool, does more than
this and reads as a lower share of the roofline.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Sequence, Tuple

# Published peaks per device kind, as JAX names the kind.
PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
    # int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table ({sorted(PEAKS)}); add it with its source")


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    layers: int
    vocab: int
    qkv_bias: bool
    el_bytes: int
    block: int

    @classmethod
    def of(cls, config: dict) -> "Dims":
        d = int(config["hidden_size"])
        h = int(config["num_attention_heads"])
        return cls(d=d, heads=h, kv_heads=int(config["num_key_value_heads"]),
                   head_dim=int(config.get("head_dim") or d // h),
                   ff=int(config["intermediate_size"]),
                   layers=int(config["num_hidden_layers"]),
                   vocab=int(config["vocab_size"]),
                   qkv_bias=bool(config.get("attention_bias", False)),
                   el_bytes=DTYPE_BYTES[config["torch_dtype"]],
                   block=int(config["kv_block_size"]))

    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        bias = (q + 2 * kv) if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d

    @property
    def kv_bytes_per_token(self) -> int:
        """Keys and values of one token over all layers."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.el_bytes


def token_flops(dims: Dims, keys: int, logits: bool) -> float:
    """Model FLOPs of forwarding one token that attends ``keys`` positions
    (itself included); the LM head only where its logits are used."""
    f = 2.0 * dims.layers * dims.layer_matmul_params
    f += 4.0 * dims.layers * dims.heads * dims.head_dim * keys
    if logits:
        f += 2.0 * dims.d * dims.vocab
    return f


def prefill_flops(dims: Dims, start: int, end: int) -> float:
    """Positions [start, end) of a prompt forwarded over a prefix of
    ``start`` cached tokens; logits of the last position only."""
    n = end - start
    if n <= 0:
        return 0.0
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return (2.0 * dims.layers * dims.layer_matmul_params * n
            + 4.0 * dims.layers * dims.heads * dims.head_dim * keys
            + 2.0 * dims.d * dims.vocab)


def decode_weight_bytes(dims: Dims, rows: int) -> float:
    """Every layer weight, the final norm and the LM head once per step,
    plus the ``rows`` embedding rows looked up."""
    return float((dims.layers * dims.layer_params + dims.d
                  + dims.vocab * dims.d + rows * dims.d) * dims.el_bytes)


def decode_step_need(dims: Dims,
                     rows: Sequence[Tuple[int, Sequence]]) -> Tuple[float,
                                                                    float]:
    """(FLOPs, bytes) one decode step needs.

    ``rows`` holds, per live sequence, ``(ctx, blocks)``: the tokens already
    in its context and the ids of the blocks that hold them, in order.
    Entries past ``ceil(ctx / block)`` are ignored, so a block table padded
    to the pool's full width counts the same as one cut to the context.
    Rows that hold the same block id share it: its keys and values are
    read once.
    """
    flops = 0.0
    tokens_in: Dict = {}
    for ctx, blocks in rows:
        flops += token_flops(dims, ctx + 1, logits=True)
        n = -(-ctx // dims.block)
        for j, b in enumerate(list(blocks)[:n]):
            filled = min(dims.block, ctx - j * dims.block)
            tokens_in[b] = max(tokens_in.get(b, 0), filled)
    kv_read = sum(tokens_in.values()) * dims.kv_bytes_per_token
    kv_write = len(rows) * dims.kv_bytes_per_token
    return flops, decode_weight_bytes(dims, len(rows)) + kv_read + kv_write


def least_time(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def chain_ids(tokens: Iterable[int], block: int) -> list:
    """Ids of the blocks of a token sequence, equal exactly where two
    sequences agree on every token up to the end of that block."""
    toks = list(tokens)
    out, h = [], None
    for j in range(0, len(toks), block):
        h = hash((h, tuple(toks[j:j + block])))
        out.append(h)
    return out
