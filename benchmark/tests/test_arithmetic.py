"""The yardstick's own arithmetic: the spread, the seeded feed, counts."""

import os

import pytest

import counts
import stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_spread_is_the_contracts():
    import statistics
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def test_feed_same_seed_same_images_other_seed_other_images():
    import data
    a, la = data.images_u8(32, 3000000000)
    b, lb = data.images_u8(32, 3000000000)
    c, _ = data.images_u8(32, 7)
    assert (a == b).all() and (la == lb).all()
    assert a.shape == c.shape and (a != c).any()
    tokens = data.pixel_tokens(data.normalize(a), 16)
    assert tokens.shape == (32, 784) and tokens.min() >= 0 and tokens.max() <= 15


PIXEL_LM = dict(embed_dim=1024, num_layers=8, num_heads=8, kv_heads=2, mlp_ratio=4,
                seq_len=784, vocab_size=17, rope=True)


def test_lm_counts_against_hand_worked_values():
    # per block: q 1024x1024+1024, kv 1024x512+512, out 1024x1024+1024,
    # up 1024x4096+4096, down 4096x1024+1024, two LayerNorms 4x1024
    block = 1049600 + 524800 + 1049600 + 4198400 + 4195328 + 4096
    c = counts.lm_param_counts(PIXEL_LM)
    assert c["block"] == block == 11021824
    assert c["total"] == 8 * block + 17 * 1024 + (1024 * 17 + 17 + 2048) == 88211473
    # forward matmul FLOPs of one token over a context of 392.5 keys
    dense = 2 * (1024 * 1024 + 1024 * 512 + 1024 * 1024 + 2 * 1024 * 4096)
    per_token = 8 * (dense + 4 * 392.5 * 1024) + 2 * 1024 * 17
    assert counts.lm_forward_flops_per_token(PIXEL_LM, 392.5) == per_token
    assert counts.lm_train_flops_per_example(PIXEL_LM) == 3 * 784 * per_token
    assert counts.lm_train_flops_per_example(PIXEL_LM) == pytest.approx(4.4466e11, rel=1e-4)


def test_memory_peak_counts_the_runtimes_reservation(monkeypatch):
    """The allocator's peak holds arrays only; a reading taken in the window
    adds what the runtime has reserved for the loaded programs' temporaries.
    Numbers as the v5e gave them for `lm_train_b16`; the fullest chip counts."""
    import jax

    import harness

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip({"bytes_in_use": 1721942016, "bytes_reserved": 5445632000,
                   "peak_bytes_in_use": 2971595776}), Chip({"bytes_in_use": 7}), Chip(None)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    assert harness.memory_now_bytes() == 1721942016 + 5445632000
    assert harness.memory_peak_bytes() == 2971595776
    assert harness.memory_peak_bytes([harness.memory_now_bytes()]) == 7167574016
