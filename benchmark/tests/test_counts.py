"""The counts of ``reducers/counts.py`` against hand-worked numbers."""

import pytest

from benchmark.harness import common, weights
from benchmark.reducers import counts


@pytest.fixture(scope="module")
def models():
    return {n: common.load_cell(c)["model"]
            for n, c in (("gpt2", "gpt2m-train-b4"),
                         ("sc2", "sc2-3b-serve-code"))}


def test_parameters(models):
    # 24 x (4 LN vectors + qkv + out + ff) + embed + pos + ln_f + untied head
    assert weights.n_params(models["gpt2"]) == 406_286_336
    assert weights.n_params(models["sc2"]) == 3_181_366_272


def test_train_flops_per_token(models):
    m = models["gpt2"]
    assert counts.matmul_params(m) == 24 * 12 * 1024 ** 2 + 1024 * 50257
    # 6 x 353.5 M + 12 x 24 x 1024 x 1024
    assert counts.train_flops_per_token(m, 1024) == pytest.approx(
        6 * 353_453_056 + 12 * 24 * 1024 * 1024)
    assert counts.train_flops_per_token(m, 1024) / 1e9 == pytest.approx(
        2.42, abs=0.01)


def test_serving_bytes(models):
    m = models["sc2"]
    # 30 layers x K,V x 2 KV heads x 128 x 2 bytes = 30 KiB a cached token
    assert counts.kv_bytes_per_token(m) == 30 * 2 * 2 * 128 * 2 == 30720
    # all but the 49152 x 3072 embedding table, in bf16
    assert counts.weight_bytes(m) == (3_181_366_272 - 49152 * 3072) * 2
    # one decoded token at context 1000: 2 x 3.03 G + 4 x 30 x 3072 x 1000
    assert counts.forward_flops_per_token(m, 1000) == pytest.approx(
        2 * counts.matmul_params(m) + 368_640_000)


def test_request_flops_sums_tokens(models):
    m = models["sc2"]
    whole = counts.request_flops(m, 100, 20)
    by_token = sum(counts.forward_flops_per_token(m, t + 1)
                   for t in range(120))
    assert whole == pytest.approx(by_token)
