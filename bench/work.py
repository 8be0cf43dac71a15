"""Work counts from shapes, and the chips' peaks.

The counts are the algorithm's own minimum, not what an implementation
happens to do: a kernel that does more work than this reads a lower share
of its roofline.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")
F32 = 4


def suffix_sum(rows: int, n: int) -> dict:
    """Exclusive suffix sum s[r, i] = sum_{j>i} w[r, j] over ``rows`` rows of
    ``n`` float32 values: one read of w and one write of s per element, and
    n - 1 additions per row."""
    return {"flops": float(rows * max(n - 1, 0)),
            "bytes": float(2 * F32 * rows * n)}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_share(work: dict, seconds: float, device_kind: str) -> float:
    """Least time the chip could take for ``work`` over ``seconds``, in %:
    the larger of operations over peak FLOP/s and bytes over peak bytes/s."""
    peak = peaks(device_kind)
    least = max(work["flops"] / peak["flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def mlp_weights(dims) -> int:
    """Weights of the dense layers ``[(fan_in, fan_out), ...]``; biases
    are left out: no matmul touches them."""
    return int(sum(a * b for a, b in dims))


def fl_training(weights: int, local_samples: float, mapped_samples: float,
                local_steps: int, server_steps: int,
                val_samples: float) -> dict:
    """Model FLOPs of FL rounds on a dense classifier of ``weights``
    weights: 6 per weight per weighted sample per full-batch SGD step (2 in
    the forward pass, 4 in the backward), on the clients' unmapped samples
    (``local_steps`` steps) and the twin's mapped ones (``server_steps``),
    and 2 per weight per sample of each validation forward pass
    (``val_samples`` = validation set x passes).  Zero-weighted slots
    (padding, the other side of the DT split) are not work."""
    sgd = local_steps * local_samples + server_steps * mapped_samples
    return {"flops": float(6 * weights * sgd + 2 * weights * val_samples)}
