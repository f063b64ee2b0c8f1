"""Operations and bytes the work needs, computed from shapes alone.

`model_flops_per_token` is the model FLOP count of PaLM (Chowdhery et al.
2022, appendix B): 6 N + 12 L H hd S per token for forward and backward,
where N counts the matmul parameters (attention projections, MLP and the
output head; the input embedding is a lookup and is left out) and the
attention term counts every score of an S x S map, as PaLM does. Recomputed
work (rematerialisation) is not counted.

The quantize kernel moves bytes and does a handful of vector operations per
value. Its least time is the larger of its HBM bytes over the HBM bandwidth
and its operations over the FLOP peak; the bytes come from the shapes and
memory spaces of the call as the trace names it, since XLA may hold a
kernel's operands on chip, where no published peak bounds them.
"""
from __future__ import annotations

import re
from typing import List, Tuple


def _dims(cfg: dict) -> Tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, cfg["num_hidden_layers"], h, kh, hd, cfg["intermediate_size"], cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """N: matmul parameters of a dense GQA decoder with a gated MLP."""
    d, layers, h, kh, hd, f, v = _dims(cfg)
    per_layer = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * f
    return layers * per_layer + d * v


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, layers, h, kh, hd, f, v = _dims(cfg)
    return 6.0 * matmul_params(cfg) + 12.0 * layers * h * hd * seq_len


# Vector operations per f32 value the quantize kernel does (|x|, a max, a
# divide, a round and a clip), for the FLOP side of its roofline.
QUANT_OPS_PER_VALUE = 5

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([0-9,]*)\](\{[^}]*\})?")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def hlo_buffers(text: str) -> List[Tuple[str, int, bool]]:
    """(dtype, elements, in HBM) of the results and operands of one HLO
    instruction as the trace names it. A layout with `S(n)` puts the
    buffer in another memory space than HBM (on-chip memory); attributes
    after the operand list are not read."""
    head = text.split(", custom_call_target", 1)[0]
    out = []
    for dtype, dims, layout in _SHAPE.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n, "S(" not in (layout or "")))
    return out


def hbm_bytes(text: str) -> int:
    """Bytes an instruction reads from and writes to HBM, from its shapes."""
    return sum(_BYTES[d] * n for d, n, hbm in hlo_buffers(text) if hbm)


def quantize_roofline_s(text: str, peak: dict) -> Tuple[float, str]:
    """(least time, its bound) of one `quantize_blocks` call: the larger of
    its HBM bytes over HBM bandwidth and its vector operations over the
    bf16 FLOP peak. Buffers held on chip (`S(1)`) add no HBM bytes."""
    values = max((n for d, n, _ in hlo_buffers(text) if d == "f32"), default=0)
    t_bytes = hbm_bytes(text) / peak["hbm_bytes_per_s"]
    t_ops = QUANT_OPS_PER_VALUE * values / peak["bf16_flops"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "flops")
