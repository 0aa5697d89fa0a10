"""Operation and byte counts from shapes, and the peak table."""
from __future__ import annotations

import os

import pytest

from bench import common, counts, gen


def mamba():
    return gen.model_of(common.load_json(
        os.path.join(common.BENCH_DIR, "configs", "mamba2-130m.dp4.json")))


def test_mamba2_130m_has_its_published_parameter_count():
    # 129.1M with the program's 50,432-row vocabulary; 50,288 rows as published
    assert counts.n_params(mamba()) == 50288 * 768 + 768 + 24 * (
        768 + 768 * 3352 + 1792 * 4 + 1792 + 3 * 24 + 1536 + 1536 * 768)
    assert counts.n_params(mamba()) == pytest.approx(129.0e6, rel=2e-3)


def test_train_flops_per_token_add_the_ssd_scan_to_6n():
    m = mamba()
    ssd = counts.ssd_flops_per_token(m)
    # per layer and token, forward: 2 [128.5 * 128 + 24 * 128.5 * 64 + 2 * 24 * 128 * 64]
    assert ssd == pytest.approx(3 * 24 * 2 * (128.5 * 128 + 24 * 128.5 * 64 + 2 * 24 * 8192))
    assert counts.mamba2_train_flops_per_token(m) == pytest.approx(6 * counts.n_params(m) + ssd)


def test_srht_gram_apply_is_bound_by_bytes_at_the_exchange_shape():
    flops, nbytes = counts.srht_gram_apply(n=4, c=126075, c_draw=126075, d=1024)
    assert nbytes == 4 * 1024 * 126075 * (2 + 8)
    assert flops == 4 * 126075 * (2 * 1024 * 10 + 4 * 1024)
    peaks = common.load_json(os.path.join(common.BENCH_DIR, "peaks.json"))["TPU v5 lite"]
    assert nbytes / peaks["hbm_bytes_per_s"] > 50 * flops / peaks["bf16_flops_per_s"]


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = common.load_json(os.path.join(common.BENCH_DIR, "peaks.json"))
    for kind, p in peaks.items():
        assert p["source"] and p["bf16_flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0, kind
