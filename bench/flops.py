"""Operations and bytes the work needs, counted from shapes and live lengths.

Everything here is a lower bound on what any implementation must do, so a
share of a roofline or of a peak built on it cannot pass 100% unless the
time leaves out part of the work.

A step is described by its rows: (start, qlen) per row, where the row runs
`qlen` query tokens at absolute positions start .. start + qlen - 1 and each
query attends causally to every earlier position of its own sequence.
"""

from __future__ import annotations

from typing import Iterable, Tuple

Row = Tuple[int, int]


def dims(model: dict):
    d = int(model["n_embd"])
    return dict(L=int(model["n_layer"]), d=d, H=int(model["n_head"]),
                hd=d // int(model["n_head"]),
                ff=int(model.get("n_inner") or 4 * d),
                V=int(model["vocab_size"]))


def dense_params(model: dict) -> int:
    """Weights every token multiplies by, outside the unembedding: QKV and
    output projections and the MLP of every layer."""
    m = dims(model)
    return m["L"] * (4 * m["d"] * m["d"] + 2 * m["d"] * m["ff"])


def keys_seen(start: int, qlen: int) -> int:
    """Sum over the row's queries of the keys each sees: sum_{p} (p + 1)."""
    return qlen * start + qlen * (qlen + 1) // 2


def model_flops(model: dict, rows: Iterable[Row], logits_rows: int) -> float:
    """Model FLOPs of one step: 2 per weight per token, attention's QK^T and
    PV at the live context, and one unembedding per row that emits a
    token."""
    m = dims(model)
    n = dense_params(model)
    total = 0.0
    for start, qlen in rows:
        total += 2.0 * n * qlen
        total += 4.0 * m["L"] * m["d"] * keys_seen(start, qlen)
    return total + 2.0 * m["d"] * m["V"] * logits_rows


def attn_cost(model: dict, rows: Iterable[Row], *, kv_itemsize: int = 4,
              selected: float = 0.0) -> Tuple[float, float]:
    """(flops, bytes) of one layer's paged attention over a step's rows.

    Bytes: K and V of every live key once per row, plus q in and the output
    out, in float32. FLOPs: QK^T once and PV once over the causal span, plus
    2*hd for each product LAMP recomputes (`selected`, this layer's count).
    """
    m = dims(model)
    hd, H = m["hd"], m["H"]
    flops = 2.0 * hd * selected
    nbytes = 0.0
    for start, qlen in rows:
        live = start + qlen
        flops += 4.0 * hd * H * keys_seen(start, qlen)
        nbytes += 2.0 * live * H * hd * kv_itemsize
        nbytes += 2.0 * qlen * H * hd * 4
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The least seconds the chip could take: the larger of FLOPs over the
    bfloat16 peak and bytes over HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
