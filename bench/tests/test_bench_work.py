"""Operation and byte counts against hand counts."""
import pytest

import work


def test_ann_parameters_at_the_papers_width():
    # 54 inputs (48 lags, forecast temperature, 5 calendar), 4 x 512, 1 out
    hand = (54 * 512 + 512) + 3 * (512 * 512 + 512) + (512 + 1)
    assert hand == 816_641
    assert work.ann_params(54, 512, 4) == hand


def test_score_tick_flops():
    # 2 x 816,641 x 256 deployments x 24 steps
    assert work.ann_score_flops(256, 54, 512, 4, 24) == pytest.approx(
        10.034884608e9, rel=1e-12)


def test_fit_flops_formula():
    # 6 x params x rows x epochs x deployments; 624 rows = 28 days - 48 lags
    rows = 28 * 24 - 48
    got = work.ann_fit_flops(256, 54, 512, 4, rows, 300)
    assert got == 6 * 816_641 * rows * 300 * 256
    assert got == pytest.approx(2.35e14, rel=0.01)


def test_fleet_mlp_bytes_count_each_real_deployment_once():
    per = 4 * (816_641 + 54 + 1)
    assert work.fleet_mlp_bytes(256, 54, 512, 4) == 256 * per
    assert work.fleet_mlp_flops(256, 54, 512, 4) == 2 * 816_641 * 256
