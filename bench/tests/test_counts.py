"""FLOP and byte counts against hand counts."""
import json

import pytest

from bench import counts
from bench.spec import BENCH_DIR


def test_paper_95m_flops_per_token():
    c = json.loads((BENCH_DIR / "configs" / "paper_95m.json").read_text())
    # blocks: 32 x (4 x 384^2 + 2 x 384 x 1536) = 56,623,104; head 384 x 50304
    assert counts.matmul_params(c) == 56_623_104 + 19_316_736
    # 6 x 75,939,840 + 12 x 32 x 512 x 384 = 455,639,040 + 75,497,472
    assert counts.model_flops_per_token(c, 512) == 531_136_512


def test_peaks_table_refuses_unknown_kind():
    assert counts.chip_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.chip_peaks("TPU v9 imaginary")
