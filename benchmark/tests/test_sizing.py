from benchmark.harness import sizing


def test_fold_bytes_is_the_formula_of_perf_md():
    for k, bpn, n_limbs, n in [(16, 7, 2, 25_557_032), (256, 6, 2, 817_872), (1, 4, 1, 1)]:
        assert sizing.fold_bytes(k, bpn, n_limbs, n) == k * bpn * n + 2 * 4 * n_limbs * n


def test_batch_size_for_is_the_largest_power_of_two_that_fits():
    hbm = 16 * 2**30
    k = sizing.batch_size_for(25_557_032, 2, 7, hbm)
    assert k == 8  # the smoke's arithmetic on the whole chip
    assert max(sizing.footprint(25_557_032, 2, 7, k).values()) <= hbm
    assert max(sizing.footprint(25_557_032, 2, 7, 2 * k).values()) > hbm
    assert sizing.batch_size_for(817_872, 2, 6, hbm) == 512
