"""Peak rates of the chips the benchmark runs on, and the operations and
bytes each measured kernel needs, computed from its shapes.  A model's
parameter and FLOP counts are its family's (``families/``).

Every roofline share and utilization the benchmark reports divides by
these.  A device kind that is not in ``PEAKS`` is an error: a share of
an unknown peak is not a number.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: Keyed by ``jax.devices()[0].device_kind``.
PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s per chip.
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e"


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to PEAKS with its source") from None


def attended_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs a causal, optionally windowed, attention
    computes for one head of one sequence."""
    w = seq if not window else min(window, seq)
    # positions 0..w-1 see i+1 keys; the rest see w
    return w * (w + 1) // 2 + (seq - w) * w


#: The sizes the attention costs read.  A family whose sizes lack one
#: has attention of another shape, which these costs do not describe.
ATTN_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim")


def has_attn(m: Dict) -> bool:
    return all(k in m for k in ATTN_KEYS)


def attn_fwd_cost(batch: int, seq: int, m: Dict,
                  itemsize: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one flash-attention forward call over one layer:
    QK^T and PV on the attended pairs; q, k, v read and the output
    written once."""
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    flops = 4.0 * batch * hq * hd * attended_pairs(
        seq, m.get("sliding_window"))
    nbytes = float(itemsize * batch * seq * hd * (2 * hq + 2 * hkv))
    return flops, nbytes


def fused_update_cost(elements: int, itemsize: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one momentum apply over ``elements``: m = beta*m
    + s*g and p -= lr*m are 5 operations; p, m and g are read and p and
    m written."""
    return 5.0 * elements, float(5 * elements * itemsize)


def roofline_time(flops: float, nbytes: float,
                  pk: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take and the bound that sets it."""
    t_c, t_m = flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
