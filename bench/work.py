"""Operations and bytes that each call needs, computed from shapes.

Everything here counts the work the algorithm needs, never the padded grid a
kernel happens to run: live KV tokens per row (capped at the window on
windowed layers), the real tail of a prefill (not its bucket), and the bytes
of q, K, V and the output that must cross HBM at least once. A roofline share
is then the least time the chip could take for that work (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s) divided by the measured kernel
time, so it cannot pass 100% unless the counts here are too high.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Iterable, Optional, Sequence, Tuple, Union

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``. An unknown kind is an
    error, never a default."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    """The widths a decoder layer's work depends on. A family whose layers
    compute differently (sparse experts, latent attention) subclasses it in
    its own file and overrides the counts below."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    window: Optional[int] = None   # sliding window W: query i sees [i-W+1, i]
    kv_bytes: int = 2              # bytes per stored K/V element
    act_bytes: int = 2             # bytes per q / output element

    def visible(self, length: int) -> int:
        """KV tokens one query at context ``length`` (itself included) reads."""
        return length if self.window is None else min(length, self.window)

    def decode_attn_call(self, lengths: Sequence[int]) -> Tuple[float, float]:
        """(FLOPs, bytes) of ONE layer's decode attention over a batch whose
        live rows hold ``lengths`` KV tokens each (the new token included)."""
        hq, hkv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        flops = 0.0
        nbytes = 0.0
        for n in lengths:
            live = self.visible(int(n))
            flops += 4.0 * hq * live * hd
            nbytes += 2.0 * hkv * live * hd * self.kv_bytes
            nbytes += 2.0 * hq * hd * self.act_bytes
        return flops, nbytes

    def prefill_pairs(self, prefix: int, tail: int) -> int:
        """(query, key) pairs a tail of ``tail`` tokens after ``prefix``
        cached tokens attends to, causal and windowed."""
        if self.window is None:
            return tail * prefix + tail * (tail + 1) // 2
        return sum(self.visible(prefix + i + 1) for i in range(tail))

    def prefill_attn_call(self, prefix: int, tail: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of ONE layer's prefill (``prefix`` == 0) or extend
        attention for one sequence: q and output of the tail, K/V of the
        tail and of the part of the prefix that some tail query can see."""
        hq, hkv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        flops = 4.0 * hq * hd * self.prefill_pairs(prefix, tail)
        seen_prefix = prefix if self.window is None else min(prefix, self.window - 1)
        nbytes = 2.0 * tail * hq * hd * self.act_bytes
        nbytes += 2.0 * (seen_prefix + tail) * hkv * hd * self.kv_bytes
        return flops, nbytes

    def matmul_params(self) -> int:
        """Weights one token multiplies through in one layer (q/k/v/o +
        SwiGLU)."""
        d, hq, hkv, hd = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim
        return d * hd * (hq + 2 * hkv) + hq * hd * d + 3 * d * self.d_ff


@dataclasses.dataclass(frozen=True)
class Stack:
    """A model's layers as groups of alike layers: ``(count, layer)`` each,
    ``layer`` a ``Shape`` (or a family's subclass) whose ``n_layers`` the
    totals below do not read."""

    vocab: int
    d_model: int
    groups: Tuple[Tuple[int, Shape], ...]


def as_stack(shape: Union[Shape, Stack]) -> Stack:
    """A plain ``Shape`` reads as one group of its ``n_layers``."""
    if isinstance(shape, Stack):
        return shape
    return Stack(shape.vocab, shape.d_model, ((shape.n_layers, shape),))


def decode_attn_call(shape: Shape, lengths: Sequence[int]) -> Tuple[float, float]:
    return shape.decode_attn_call(lengths)


def prefill_pairs(shape: Shape, prefix: int, tail: int) -> int:
    return shape.prefill_pairs(prefix, tail)


def prefill_attn_call(shape: Shape, prefix: int, tail: int) -> Tuple[float, float]:
    return shape.prefill_attn_call(prefix, tail)


def layer_matmul_params(shape: Shape) -> int:
    return shape.matmul_params()


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the least time the chip needs for this work."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def decode_least_seconds(shape: Union[Shape, Stack],
                         ticks: Iterable[Sequence[int]], peak: dict) -> float:
    """Least time of every decode-attention call of ``ticks`` (one entry per
    decode tick: the live rows' lengths), over all layers."""
    ticks = [lengths for lengths in ticks if len(lengths)]
    out = 0.0
    for count, layer in as_stack(shape).groups:
        total = 0.0
        for lengths in ticks:
            total += least_seconds(*layer.decode_attn_call(lengths), peak)
        out += total * count
    return out


def prefill_least_seconds(shape: Union[Shape, Stack],
                          prefills: Iterable[Tuple[int, int]], peak: dict) -> float:
    """Least time of every prefill/extend attention call, over all layers.
    ``prefills``: (prefix tokens, tail tokens) per prefilled sequence."""
    prefills = list(prefills)
    out = 0.0
    for count, layer in as_stack(shape).groups:
        total = 0.0
        for prefix, tail in prefills:
            total += least_seconds(*layer.prefill_attn_call(prefix, tail), peak)
        out += total * count
    return out


def model_flops(shape: Union[Shape, Stack], prefills: Sequence[Tuple[int, int]],
                ticks: Sequence[Sequence[int]]) -> float:
    """FLOPs the model needs for these prefills and decode ticks: two per
    weight per token through the layers, the head once per prefill (only the
    last position's logits are needed) and once per decoded token, plus
    attention. Embedding lookups are gathers and count nothing."""
    stack = as_stack(shape)
    per_tok = sum(2.0 * layer.matmul_params() * count for count, layer in stack.groups)
    head = 2.0 * stack.d_model * stack.vocab
    flops = 0.0
    for prefix, tail in prefills:
        flops += tail * per_tok + head
        flops += sum(count * layer.prefill_attn_call(prefix, tail)[0]
                     for count, layer in stack.groups)
    for lengths in ticks:
        flops += len(lengths) * (per_tok + head)
        flops += sum(count * layer.decode_attn_call(lengths)[0]
                     for count, layer in stack.groups)
    return flops
