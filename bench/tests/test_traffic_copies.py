"""The benchmark's copies of the program's generators draw what the
program's own would: the copies exist so that the yardstick cannot move
with the program, and this test says when the two part."""
import numpy as np

from lib import traffic_gen


def test_arrival_masks_match_the_straggler_scheduler():
    from repro.core.scheduler import StragglerConfig, StragglerScheduler

    for seed in (0, 7, 2 ** 31 + 3):
        cfg = StragglerConfig(n_workers=4, s_active=3, tau=10,
                              n_stragglers=1, straggler_slowdown=5.0,
                              seed=seed)
        want = StragglerScheduler(cfg).precompute(200)
        got = traffic_gen.arrival_schedule(4, 3, 10, 1, 5.0, 200, seed)
        np.testing.assert_array_equal(got[0], want.active)
        np.testing.assert_array_equal(got[1], want.sim_time)
        np.testing.assert_array_equal(got[2], want.max_staleness)


def test_large_seeds_give_distinct_keys():
    import jax

    a = jax.random.key_data(traffic_gen.seed_key(2 ** 31 + 1))
    b = jax.random.key_data(traffic_gen.seed_key(1))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_regression_data_matches_the_program_stand_in():
    from lib.common import load_json, load_module
    from repro.data.synthetic import make_regression

    conf = load_json("configs", "rhpo-whitewine.json")["problem"]
    ref = load_module("configs", "rhpo-whitewine.py")
    for seed in (0, 3):
        want = make_regression(conf["dataset"], conf["n_workers"], seed=seed)
        got = ref.make_data(conf, seed)
        for k, w in (("xtr", want.x_train), ("ytr", want.y_train),
                     ("xval", want.x_val), ("yval", want.y_val)):
            np.testing.assert_array_equal(got[k], w)

