"""The yardstick's FLOP and byte counts against a hand count."""
import json
from pathlib import Path

import pytest

from bench.flops import FlopModel

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

# hand counts of each layer's matmul weights at the published widths:
# q, o: d x (heads * head_dim); k, v: d x (kv_heads * head_dim);
# gate, up, down: d x d_ff. The totals add the norms and match the served
# bytes: 2 B per matmul weight and 4 B per norm gain (granite: 5066731520 +
# 663552 bytes on the chip; internlm2: 3778420736 bytes compiled for v5e)
HAND = {
    "granite-3-2b": dict(layers=40, layer=2048 * 64 * (2 * 32 + 2 * 8)
                         + 3 * 2048 * 8192, unembed=2048 * 49155,
                         params=2_533_531_648),
    "internlm2-1.8b": dict(layers=24, layer=2048 * 128 * (2 * 16 + 2 * 8)
                           + 3 * 2048 * 8192, unembed=2048 * 92544,
                           params=1_889_110_016),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_flops_per_token_is_twice_the_matmul_weights(name):
    fm = FlopModel.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))
    h = HAND[name]
    assert fm.layer_matmul_params() == h["layer"]
    assert fm.matmul_params() == h["layers"] * h["layer"] + h["unembed"]
    assert fm.param_count() == h["params"]
    # a token at position 0 attends to itself only
    attn0 = 4 * fm.layers * fm.heads * fm.head_dim
    assert fm.token_flops(0, 1) == 2 * h["layers"] * h["layer"] + attn0
    assert fm.logits_flops() == 2 * h["unembed"]


def test_attention_counts_the_real_context_of_each_token():
    fm = FlopModel(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                   d_ff=16, vocab=10, tied=True)
    per_key = 4 * 2 * 2 * 4
    dense = 2 * 2 * fm.layer_matmul_params()
    # positions 3, 4: 4 + 5 keys
    assert fm.token_flops(3, 5) == 2 * dense + per_key * 9
    assert fm.token_flops(0, 5) == fm.token_flops(0, 3) + fm.token_flops(3, 5)


def test_step_bytes():
    fm = FlopModel(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                   d_ff=16, vocab=10, tied=True)
    kv = 2 * 2 * 1 * 4 * 2
    assert fm.kv_bytes_per_token() == kv
    assert fm.step_bytes(100, 3) == fm.weight_bytes() + kv * 103
