"""Registry completeness + parameter-count sanity vs the named sizes."""

import pytest

from repro.configs import REGISTRY, cells, get_config
from repro.models import build

EXPECTED_B = {  # nameplate sizes (rough bands)
    "llama-3.2-vision-11b": (8.5, 11.5),   # text backbone of the 11B (vision stub)
    "smollm-135m": (0.11, 0.16),
    "qwen2.5-3b": (2.6, 3.5),
    "qwen2-72b": (65, 80),
    "gemma3-1b": (0.85, 1.3),
    "whisper-medium": (0.6, 1.0),          # our enc-dec variant
    "zamba2-2.7b": (2.2, 3.1),
    "deepseek-moe-16b": (14, 19),
    "deepseek-v3": (660, 685),             # 671B main model, MTP left out
    "llama4-scout-17b-a16e": (95, 115),    # 17B active / ~109B total
    "xlstm-125m": (0.05, 0.2),   # lean mLSTM blocks, d_ff=0 per assignment
}


def test_all_ten_archs_registered():
    assert len(REGISTRY) == 11


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_param_counts(name):
    model = build(get_config(name))
    lo, hi = EXPECTED_B[name]
    got = model.n_params / 1e9
    assert lo <= got <= hi, f"{name}: {got:.2f}B not in [{lo},{hi}]"


def test_cells_cover_assignment():
    live, skipped = cells()
    assert len(live) + len(skipped) == 44
    # long_500k runs only for sub-quadratic archs
    longs = [a for a, s in live if s == "long_500k"]
    assert set(longs) == {"gemma3-1b", "zamba2-2.7b", "xlstm-125m"}
    assert len(skipped) == 8
