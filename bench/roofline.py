"""Operations and bytes the computation needs, counted from shapes.

These are the work a kernel has to do, not what it happens to do: a
later kernel that does the same job with fewer bytes is measured against
the same count and cannot pass 100% of its roofline.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench.data import COLORS, level_shapes

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """The peaks of one chip, by JAX's ``device_kind``. An unknown device
    is an error: a default would pass off one chip's numbers as
    another's."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def cnn_flops(level: dict) -> float:
    """Forward multiply-adds x 2 of one cascade level's CNN per image:
    3x3 SAME convs at their input size, the dense layer and the output
    unit. Pooling, relu and the bias adds are not counted."""
    hw, cin = level["resolution"], COLORS[level["color"]]
    total = 0.0
    for _ in range(level["conv_layers"]):
        total += 2.0 * hw * hw * 9 * cin * level["conv_nodes"]
        cin, hw = level["conv_nodes"], hw // 2
    total += 2.0 * hw * hw * cin * level["dense_nodes"]
    return total + 2.0 * level["dense_nodes"]


def param_bytes(level: dict) -> int:
    s = level_shapes(level)
    n = sum(a * b * c * d + e for (a, b, c, d), (e,) in s["conv"])
    n += s["dense"][0] * s["dense"][1] + s["dense"][1]
    return 4 * (n + s["out"][0] + 1)


def pooled_levels(base: int, out_res, stage0: dict) -> list[int]:
    return sorted((set(out_res) | {stage0["resolution"]}) - {base},
                  reverse=True)


def stage0_kernel_work(batch: int, base: int, out_res, stage0: dict
                       ) -> tuple[float, float]:
    """(operations, bytes) of one ``fused_pyramid_stage0`` call over
    ``batch`` frames of ``base`` px: every pooled level adds each base
    value once; a grey or single-channel stage-0 input projects its
    pixels (5 operations per grey pixel); then the stage-0 CNN. Bytes:
    the base frames at 1 byte per channel (the corpus is uint8-valued),
    each emitted level and the logit at float32, and the stage-0 weights
    once."""
    res0, color = stage0["resolution"], stage0["color"]
    per_image = 3.0 * base * base * len(pooled_levels(base, out_res,
                                                      stage0))
    if color == "gray":
        per_image += 5.0 * res0 * res0
    per_image += cnn_flops(stage0)
    bytes_per_image = 3.0 * base * base + 4.0
    bytes_per_image += sum(4.0 * 3 * r * r for r in out_res)
    return (batch * per_image,
            batch * bytes_per_image + param_bytes(stage0))


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Share (%) of the chip's roofline that work of ``ops`` and
    ``nbytes`` reached in ``seconds`` of device time, and which bound
    applies ("compute" or "memory"). Operations are held to the bf16
    matrix peak, the highest the chip has."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
