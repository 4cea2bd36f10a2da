"""Peaks by device kind, and the FLOP and byte counts of each configuration
at its published widths, against counts made by hand."""

from __future__ import annotations

import json

import pytest

import bench_cells  # noqa: F401  (puts bench/ on sys.path)
import peaks
from harness import HERE, load_module


def model(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]


def test_v5e_peaks_and_unknown_device():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_bert_large_1b_counts():
    ref = load_module(HERE / "configs" / "bert-large-1b.py")
    m = model("bert-large-1b")
    # per layer: q, k, v, o 4 x 1536^2 = 9,437,184; q/k/v biases 4,608;
    # MLP 2 x 1536 x 6144 + 6144 + 1536 = 18,882,048; two LayerNorms 6,144
    layer = 9_437_184 + 4_608 + 18_882_048 + 6_144
    total = 36 * layer + 30_522 * 1_536 + 2 * 1_536
    assert total == 1_066_764_288
    assert ref.n_params(m) == total
    # 6 N + 12 L d s at s = 512
    assert ref.train_flops_per_token(m, 512) == 6 * total + 12 * 36 * 1536 * 512
    # 27.6 TFLOP per 8 x 512-token step
    assert ref.train_flops_per_token(m, 512) * 4096 == pytest.approx(
        27.608e12, rel=1e-3)

