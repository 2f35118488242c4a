"""The yardstick's arithmetic against hand counts, the peaks table, and the
open-loop schedule reproduced from its seed."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import peaks, traffic, work
from perfbench_roots import REPO

MIXTRAL = json.loads((REPO / "bench/configs/mixtral-8x22b.json").read_text())
STEADY = json.loads((REPO / "bench/traffic/steady.json").read_text())


def test_peaks_known_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_peaks_unknown_kind_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(kind)


def test_spmv_least_bytes_hand_count():
    # 2^22 rows x (16 x (4 B value + 4 B column) + diag + x + y) = 140 B/row
    assert work.spmv_least_bytes(2**22, 16) == 2**22 * 140 == 587202560
    assert work.spmv_least_bytes(10, 2, val_bytes=8) == 10 * (2 * 12 + 24)


def test_mixtral_layer_params_hand_count():
    attn = 6144 * 6144 + 2 * 6144 * 1024 + 6144 * 6144     # 88,080,384
    router = 6144 * 8
    experts = 2 * 3 * 6144 * 16384                          # 603,979,776
    assert work.mixtral_layer_params(MIXTRAL) == attn + router + experts \
        == 692109312


def test_mixtral_flops_hand_count():
    # one decode token at position 99 (attends 100 keys), logits once
    f = work.mixtral_flops(MIXTRAL, tokens=1, attended=100, logit_rows=1)
    want = (2 * 2 * 692109312 + 2 * 6144 * 32768
            + 4 * 2 * 48 * 128 * 100)
    assert f == want == 3176005632
    # a 128-token chunk at the start: keys 1..128, one row of logits
    f = work.mixtral_flops(MIXTRAL, tokens=128, attended=128 * 129 // 2,
                           logit_rows=1)
    assert f == 128 * 2 * 2 * 692109312 + 2 * 6144 * 32768 + \
        4 * 2 * 48 * 128 * 8256


def test_open_loop_is_reproduced_from_its_seed():
    a = traffic.open_loop(STEADY, 3000000021, 30.0, 32768)
    b = traffic.open_loop(STEADY, 3000000021, 30.0, 32768)
    assert [(x.due, x.max_new_tokens) for x in a] == \
        [(x.due, x.max_new_tokens) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_one_multiset_of_work():
    a = traffic.open_loop(STEADY, 1, 30.0, 32768)
    b = traffic.open_loop(STEADY, 2**33 + 5, 30.0, 32768)
    n = round(STEADY["rate_per_s"] * 30)
    assert len(a) == len(b) == n
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    gaps = lambda arr: sorted(np.round(np.diff([x.due for x in arr]), 9))
    assert [x.due for x in a] != [x.due for x in b]
    for arr in (a, b):
        dues = [x.due for x in arr]
        assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 30.0
    assert len(gaps(a)) == len(gaps(b))


def test_open_loop_sizes_follow_the_mix():
    arr = traffic.open_loop(STEADY, 7, 30.0, 32768)
    p = np.array([len(x.prompt) for x in arr])
    o = np.array([x.max_new_tokens for x in arr])
    assert (p % 128 == 0).all() and p.min() >= 128 and p.max() <= 2048
    assert o.min() >= 16 and o.max() <= 256
    assert 384 <= np.median(p) <= 512            # median 384, rounded up
    assert 56 <= np.median(o) <= 72
    assert all((x.prompt >= 0).all() and (x.prompt < 32768).all()
               for x in arr)
    traffic.check_traffic(STEADY, 2304, 128)


def test_traffic_refuses_prompts_off_the_chunk():
    bad = dict(STEADY, prompt_tokens=dict(STEADY["prompt_tokens"],
                                          multiple=100))
    with pytest.raises(ValueError):
        traffic.check_traffic(bad, 2304, 128)
    with pytest.raises(ValueError):
        traffic.check_traffic(STEADY, 2048, 128)
