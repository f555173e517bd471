"""The analytic work counts against hand counts at a tiny size."""
import pytest

from lib import work


def test_afto_iteration_work_hand_count():
    """2 workers of 3 training rows and one feature, hidden 1: z1 1,
    z2 2*3*1 = 6, z3 (w0, b0, w1, b1) 4, so D = 1 + 6 + 4 + 2*(6 + 4) = 31;
    P=2, K=1, a refresh and a record every 10 iterations."""
    config = {"problem": {"n_workers": 2, "n_features": 1, "hidden": 1,
                          "n_samples": 10, "test_frac": 0.2,
                          "val_frac": 0.25},
              "hyper": {"p_max": 2, "k_inner": 1, "t_pre": 10}}
    # 10 samples: 2 test, 8 left, 2 validation, 6 training over 2 workers
    assert work.afto_cut_space(config) == (2, 31)
    w = work.afto_iteration_work(config, {"record_every": 10})
    passes = 3 + 0.3 + 0.9
    assert w["bytes_per_iter"] == pytest.approx(passes * 4 * 2 * 31)
    assert w["flops_per_iter"] == pytest.approx(passes * 2 * 2 * 31)
    assert w["cut_kernel_bytes_per_iter"] == pytest.approx(
        (1 + 0.1 + 0.4) * 4 * 2 * 31)


def test_white_wine_cut_space_is_the_configured_one():
    from lib.common import load_json

    config = load_json("configs", "rhpo-whitewine.json")
    assert work.afto_cut_space(config) == (config["cut_space"]["P"],
                                           config["cut_space"]["D"])
