"""Operations and bytes that the served algorithm needs, from shapes alone.

The counts are of the work, not of how the program does it: coded weights
are read in their coded form (4-bit codes with their per-column f32 mean and
deviation, the bf16 head that stays dense), the KV pool in its stored form at
the configuration's ``kv_bits``, for the contexts that are really attended,
and matmuls and attention at two operations per multiply-add.  A program that
dequantizes a dense copy, or reads padded pages, does more than this; that
is what its roofline share shows.

``c`` is a configuration file's dict (``bench/configs/<config>.json``).
"""

from __future__ import annotations

import json
import pathlib
from typing import Iterable

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
STAT_BYTES = 4                  # f32 mean and deviation per coded column
KV_STAT_BYTES = 2               # bf16 mean and deviation per (row, head)


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"])


def layer_matmuls(c: dict):
    """(fan_in, fan_out) of one layer's matmul weights."""
    _, d, f, H, KV, hd, _ = _dims(c)
    return [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d),
            (d, f), (d, f), (f, d)]


def matrix_bytes(fan_in: int, fan_out: int, w_bits: int) -> int:
    """Served bytes of one matmul weight.  Coded (4/8 bits) where the
    trailing dimension packs; otherwise dense bf16."""
    if w_bits >= 16 or (w_bits == 4 and fan_out % 2):
        return 2 * fan_in * fan_out
    return fan_in * fan_out * w_bits // 8 + 2 * STAT_BYTES * fan_out


def weight_bytes(c: dict) -> int:
    """Bytes of the whole served tree: layers, embedding, head, norms."""
    L, d, _, _, _, _, V = _dims(c)
    w = c["serving"]["w_bits"]
    layer = sum(matrix_bytes(i, o, w) for i, o in layer_matmuls(c))
    norms = 4 * (2 * L * d + d)
    return (L * layer + matrix_bytes(V, d, w) + matrix_bytes(d, V, w)
            + norms)


def step_weight_bytes(c: dict, rows: int) -> int:
    """Weight bytes one step over ``rows`` tokens must read: every layer,
    the head, the norms, and only the embedding rows it looks up."""
    L, d, _, _, _, _, V = _dims(c)
    w = c["serving"]["w_bits"]
    emb_row = matrix_bytes(V, d, w) / V
    return int(weight_bytes(c) - matrix_bytes(V, d, w)
               + min(rows, V) * emb_row)


def token_kv_bytes(c: dict) -> int:
    """Pool bytes of one token across all layers (K and V)."""
    L, _, _, _, KV, hd, _ = _dims(c)
    bits = c["serving"]["kv_bits"]
    per_head = 2 * hd if bits >= 16 else hd * bits // 8 + 2 * KV_STAT_BYTES
    return 2 * L * KV * per_head


def matmul_flops_per_token(c: dict) -> int:
    L, d, _, _, _, _, V = _dims(c)
    return 2 * (L * sum(i * o for i, o in layer_matmuls(c)) + d * V)


def attention_flops(c: dict, context: int) -> int:
    """QK and PV of one token against ``context`` positions, all layers."""
    L, _, _, H, _, hd, _ = _dims(c)
    return 4 * L * H * hd * context


def decode_step(c: dict, contexts: Iterable[int]):
    """(flops, bytes) of one decode step whose rows attend ``contexts``
    positions each (the new row's own position included)."""
    ctx = list(contexts)
    rows, total = len(ctx), sum(ctx)
    flops = rows * matmul_flops_per_token(c) + attention_flops(c, total)
    kv = token_kv_bytes(c)
    return flops, step_weight_bytes(c, rows) + total * kv + rows * kv


def least_seconds(flops: float, nbytes: float, pk: dict):
    """(seconds, bound): the larger of the compute and the memory time."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")

