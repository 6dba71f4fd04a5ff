"""Operations and bytes the algorithms need, from model shapes and token
counts.  They count what the mathematics requires — live context, real
tokens — never what a kernel happens to read (table width, padded pages,
idle lanes), so a kernel that skips work cannot read above its roofline.

``dims`` uses the serving program's field names (``d_model``,
``num_layers``, ...), as a configuration file maps them.  A FLOP is one
multiply or one add; bf16 tensors take 2 bytes an element.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2
F32 = 4


def head_dim(dims: Dict) -> int:
    return dims.get("head_dim") or dims["d_model"] // dims["num_heads"]


# ------------------------------------------------------------ dense (GQA)
def dense_layer_weights(dims: Dict) -> int:
    """Weights one attention + SwiGLU layer multiplies by per token."""
    d, f = dims["d_model"], dims["d_ff"]
    hq, hkv, hd = dims["num_heads"], dims["num_kv_heads"], head_dim(dims)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def dense_token_flops(dims: Dict, ctx: int, logits: bool) -> int:
    """One token through every layer, attending over ``ctx`` positions
    (itself included), with the vocabulary projection when ``logits``."""
    hq, hd = dims["num_heads"], head_dim(dims)
    per_layer = 2 * dense_layer_weights(dims) + 4 * ctx * hq * hd
    out = dims["num_layers"] * per_layer
    if logits:
        out += 2 * dims["d_model"] * dims["vocab_size"]
    return out


def paged_attention_counts(dims: Dict, ctxs: Iterable[int]) -> Dict[str, int]:
    """Decode attention for one token per entry of ``ctxs`` (each the live
    context it attends over), in every layer: ``q . k`` and ``p . v`` over
    the live keys, and the bytes of those keys and values, the query and
    the output."""
    hq, hkv, hd, n = (dims["num_heads"], dims["num_kv_heads"],
                      head_dim(dims), dims["num_layers"])
    flops = bytes_ = 0
    for ctx in ctxs:
        flops += n * 4 * ctx * hq * hd
        bytes_ += n * (2 * ctx * hkv * hd * BF16 + 2 * hq * hd * BF16)
    return {"flops": flops, "bytes": bytes_}


# ------------------------------------------------------------ Mamba2 (SSD)
def ssm_sizes(dims: Dict) -> Dict[str, int]:
    d_inner = dims["ssm_expand"] * dims["d_model"]
    n = dims["ssm_state"]
    heads = d_inner // dims["ssm_head_dim"]
    conv_dim = d_inner + 2 * n
    return {"d_inner": d_inner, "heads": heads, "state": n,
            "head_dim": dims["ssm_head_dim"], "conv_dim": conv_dim,
            "in_proj": 2 * d_inner + 2 * n + heads}


def ssd_scan_token_flops(dims: Dict) -> int:
    """The recurrence for one token in one layer, head by head:
    ``h = exp(dt A) h + (dt x) B^T`` and ``y = C h + D x``."""
    s = ssm_sizes(dims)
    return s["heads"] * (5 * s["state"] * s["head_dim"] + 3 * s["head_dim"])


def ssd_scan_counts(dims: Dict, tokens: int) -> Dict[str, int]:
    """The SSD scan over ``tokens`` prompt tokens in every layer: its
    inputs (x and y in bf16, dt in f32 per head; B and C in bf16, shared by
    the heads) and its operations.  The carried state stays on chip."""
    s = ssm_sizes(dims)
    per_tok = (s["heads"] * (2 * s["head_dim"] * BF16 + F32)
               + 2 * s["state"] * BF16)
    n = dims["num_layers"] * tokens
    return {"flops": n * ssd_scan_token_flops(dims), "bytes": n * per_tok}


def ssm_token_flops(dims: Dict, logits: bool) -> int:
    """One token through every Mamba2 layer: in-projection, depthwise conv,
    the scan, the gated norm and the out-projection."""
    s = ssm_sizes(dims)
    d = dims["d_model"]
    per_layer = (2 * d * s["in_proj"] + 2 * dims["conv_width"] * s["conv_dim"]
                 + ssd_scan_token_flops(dims) + 6 * s["d_inner"]
                 + 2 * s["d_inner"] * d)
    out = dims["num_layers"] * per_layer
    if logits:
        out += 2 * d * dims["vocab_size"]
    return out


def token_flops(dims: Dict, family: str, ctx: int, logits: bool) -> int:
    """Model FLOPs of one token of ``family`` (``dense`` or ``ssm``)."""
    if family == "dense":
        return dense_token_flops(dims, ctx, logits)
    if family == "ssm":
        return ssm_token_flops(dims, logits)
    raise ValueError(f"unknown family {family!r}")
