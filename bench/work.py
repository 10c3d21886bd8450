"""Operations and HBM bytes the algorithms need, counted from shapes.

Every count is a lower bound on what any implementation must do: each
operand is read once and each result written once, at the dtype it is
stored in, and no operation is counted twice or recomputed.  A sparse
operand of ``nnz`` stored entries in compressed-row form costs its
values, one column index per entry and ``rows + 1`` row pointers.
Elementwise work (activations, softmax, the loss) is not counted, so a
share of a peak computed from these numbers can only read low.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, List, Optional, Tuple

F32 = 4
I32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


ZERO = Work(0.0, 0.0)


def total(works: Iterable[Work]) -> Work:
    out = ZERO
    for w in works:
        out = out + w
    return out


def sparse_operand_bytes(nnz: int, rows: int, value_bytes: int = F32,
                         index_bytes: int = I32) -> float:
    return nnz * (value_bytes + index_bytes) + (rows + 1) * index_bytes


def pattern_bytes(nnz: int, rows: int, index_bytes: int = I32) -> float:
    """A 0/1 pattern needs its indices only."""
    return nnz * index_bytes + (rows + 1) * index_bytes


def spmm(nnz: int, rows: int, cols: int, d: int, value_bytes: int = F32,
         dense_bytes: int = F32) -> Work:
    """Y[rows, d] = A[rows, cols] @ H[cols, d]."""
    return Work(2.0 * nnz * d,
                sparse_operand_bytes(nnz, rows, value_bytes)
                + (cols + rows) * d * dense_bytes)


def sddmm(nnz: int, rows: int, cols: int, k: int,
          dense_bytes: int = F32, out_bytes: int = F32) -> Work:
    """S = pattern(A) * (B[rows, k] @ C[k, cols]), one value per entry."""
    return Work(2.0 * nnz * k,
                pattern_bytes(nnz, rows) + (rows + cols) * k * dense_bytes
                + nnz * out_bytes)


def attention(nnz: int, n: int, d: int, dense_bytes: int = F32) -> Work:
    """Single-head graph attention over a pattern with per-node scores:
    one add per edge for the score, 2*d per edge to aggregate."""
    return Work(nnz * (1.0 + 2.0 * d),
                pattern_bytes(nnz, n) + 2 * n * dense_bytes
                + 2 * n * d * dense_bytes)


def dense(m: int, k: int, n: int, dtype_bytes: int = F32) -> Work:
    """C[m, n] = A[m, k] @ B[k, n]."""
    return Work(2.0 * m * k * n, (m * k + k * n + m * n) * dtype_bytes)


def layer_dims(cfg: dict) -> List[int]:
    return ([cfg["in_features"]] + [cfg["hidden"]] * (cfg["n_layers"] - 1)
            + [cfg["n_classes"]])


def gcn_train_step(cfg: dict, n: int, nnz: int) -> Work:
    """Forward and backward of the GCN on an n-node graph whose
    normalised adjacency stores ``nnz`` entries; gradients for the
    weights, and for each layer's input except the features."""
    dims = layer_dims(cfg)
    parts = []
    for i in range(cfg["n_layers"]):
        din, dout = dims[i], dims[i + 1]
        parts += [dense(n, din, dout), spmm(nnz, n, n, dout)]   # forward
        parts += [spmm(nnz, n, n, dout), dense(din, n, dout)]   # A^T g, dW
        if i > 0:
            parts.append(dense(n, dout, din))                   # dX
    return total(parts)


def gat_train_step(cfg: dict, n: int, nnz: int) -> Work:
    """Forward and backward of the single-head GAT over a pattern of
    ``nnz`` entries: per layer H W, the two score vectors, the attention
    aggregation; backward the transposed aggregation and the sampled
    products for the attention weights, then the dense gradients."""
    dims = layer_dims(cfg)
    parts = []
    for i in range(cfg["n_layers"]):
        din, dout = dims[i], dims[i + 1]
        parts += [dense(n, din, dout), dense(n, dout, 2),
                  attention(nnz, n, dout)]
        parts += [spmm(nnz, n, n, dout), sddmm(nnz, n, n, dout),
                  dense(dout, n, 2), dense(din, n, dout)]
        if i > 0:
            parts.append(dense(n, dout, din))
    return total(parts)


TRAIN_STEP = {"gcn": gcn_train_step, "gat": gat_train_step}


# The program's Pallas kernels, by the ``name=`` their calls carry in a
# trace, and the product each computes.
KERNELS = {
    "spmm_sell": "spmm", "spmm_sell_epilogue": "spmm",
    "spmm_blockell": "spmm", "spmm_blockell_epilogue": "spmm",
    "sddmm_sell": "sddmm", "sddmm_blockcoo": "sddmm",
    "fused_graph_attention_sell": "attention",
    "fused_graph_attention_blockell": "attention",
}
_LAYOUTS = {"sell": "sell", "ell": "blockell"}


def planned_kernel(op: str, path: str, fused: Optional[str]) -> Optional[str]:
    """The kernel that a dispatch plan running on a Pallas kernel calls,
    by its op, path and fused tag; None for a path with no kernel."""
    layout = _LAYOUTS.get(path)
    if layout is None:
        return None
    if op == "spmm":
        return f"spmm_{layout}" + ("_epilogue" if fused else "")
    if op == "sddmm":
        return "sddmm_sell" if path == "sell" else "sddmm_blockcoo"
    if op == "fused_attn":
        return f"fused_graph_attention_{layout}"
    return None


_SHAPE = re.compile(r"\b(pred|bf16|f8\w*|[suf]\d+)\[([0-9,]*)\]")


def _dtype_bytes(dtype: str) -> int:
    if dtype == "pred" or dtype.startswith("f8"):
        return 1
    if dtype == "bf16":
        return 2
    return int(dtype[1:]) // 8


def hlo_shapes(text: str) -> List[Tuple[int, Tuple[int, ...]]]:
    """(bytes per element, dims) of every array shape in an HLO
    instruction's text, the result's first."""
    return [(_dtype_bytes(t), tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def _width(shape: Tuple[int, ...], n: int) -> Optional[int]:
    """The feature width of an [n, w] or [w, n] dense operand."""
    if len(shape) == 2 and n in shape and shape[0] != shape[1]:
        return shape[1] if shape[0] == n else shape[0]
    return None


def kernel_call(kernel: str, text: str, n: int, nnz: int) -> Optional[Work]:
    """Required work of one call of a program kernel on an n-node graph of
    ``nnz`` stored entries, with widths and dtypes read from the call's
    HLO text (as run, so a change of width or dtype is counted as it
    is); None where the text does not show them."""
    shapes = hlo_shapes(text)
    if not shapes:
        return None
    (out_bytes, out), operands = shapes[0], shapes[1:]
    dense = [(b, _width(s, n)) for b, s in operands if _width(s, n)]
    values = [b for b, s in operands if len(s) >= 3]
    kind = KERNELS.get(kernel)
    if kind in ("spmm", "attention"):
        d = _width(out, n)
        if d is None:
            return None
        if kind == "attention":
            return attention(nnz, n, d, dense_bytes=out_bytes)
        if not values:
            return None
        return spmm(nnz, n, n, d, value_bytes=values[0],
                    dense_bytes=out_bytes)
    if kind == "sddmm" and dense:
        widths = {w for _, w in dense}
        if len(widths) != 1:
            return None
        return sddmm(nnz, n, n, widths.pop(), dense_bytes=dense[0][0],
                     out_bytes=out_bytes)
    return None


def roofline_seconds(work: Work, peak_flops: float, peak_bytes: float):
    """(least time, the bound that sets it: "flops" or "bytes")."""
    tf, tb = work.flops / peak_flops, work.bytes / peak_bytes
    return (tf, "flops") if tf >= tb else (tb, "bytes")
