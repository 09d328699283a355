"""The work counts against hand counts of the StarCoder2 configuration."""
import json
from pathlib import Path

import pytest

from bench import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_starcoder2_parameters():
    cfg = config("starcoder2-7b-1l")
    c = counts.lm_param_counts(cfg)
    embed = 6144 * 4608  # each vocabulary table
    attn = 2 * 4608 * 36 * 128 + 2 * 4608 * 4 * 128
    mlp = 2 * 4608 * 18432
    norms = 3 * 4608
    assert c["embed"] == embed
    assert c["matmul"] == attn + mlp + embed  # the unembedding is a matmul
    assert c["total"] == 273_692_160 == embed + attn + mlp + embed + norms
    assert cfg["parameters"] == c["total"]


def test_lm_round():
    cfg = config("starcoder2-7b-1l")
    r = counts.lm_round(cfg, 4, 512, 2, 1)
    tokens = 1 * 2 * 4 * 512
    matmul = 273_692_160 - 6144 * 4608 - 3 * 4608
    attn = 12 * 1 * 36 * 128 * 512
    assert r["flops"] == (6 * matmul + attn) * tokens
    assert r["flops"] == pytest.approx(6.147e12, rel=1e-3)
    # x, g and m read, x written, per client step: 2 steps
    assert r["direction_bytes"] == 2 * 4 * 273_692_160 * 4
    assert r["fold_bytes"] == 5 * 273_692_160 * 4
