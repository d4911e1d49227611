"""The vectorized room generator is the repo's distribution."""
import numpy as np
import pytest

import scenes
from repro.data.scenes import make_scene


@pytest.mark.parametrize("seed,res,cap,ppu,n_obj", [
    (0, 32, 4096, 20000.0, 4), (3, 64, 8192, 60000.0, 2),
    (11, 48, 1024, 80000.0, 6), (5, 32, 64, 20000.0, 3)])
def test_make_room_matches_make_scene(seed, res, cap, ppu, n_obj):
    want = make_scene(seed, res, cap, points_per_unit=ppu, n_objects=n_obj)
    got = scenes.make_room(seed, res, cap, points_per_unit=ppu,
                           n_objects=n_obj)
    for name, w, g in zip(("coords", "feats", "labels", "mask"), want, got):
        assert w.shape == g.shape and w.dtype == g.dtype, name
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[3], want[3])
    # per-voxel means summed in another order: float32 rounding
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=4e-7)


def test_room_sizes_same_work_for_every_seed():
    a = scenes.room_sizes(8, 1, 45000, 0.3, 20000, 60000, 2, 8)
    b = scenes.room_sizes(8, 2, 45000, 0.3, 20000, 60000, 2, 8)
    assert sorted(a[0]) == sorted(b[0]) and sorted(a[1]) == sorted(b[1])
    assert list(a[0]) != list(b[0])
    assert min(a[0]) >= 20000 and max(a[0]) <= 60000


def test_room_with_voxels_hits_its_target():
    _, _, _, mask = scenes.room_with_voxels(4, 3000, 4, 64, 8192)
    assert abs(int(mask.sum()) - 3000) <= 0.05 * 3000


def test_fixed_sensor_frames_overlap_and_repeat():
    s = scenes.FixedSensorStream(3, 64, 8192, room_voxels=3000, n_objects=3,
                                 object_voxels=150, dropout=0.02)
    f0, f1 = s.frame(0), s.frame(1)
    np.testing.assert_array_equal(s.frame(1)[0], f1[0])

    def keys(f):
        c = f[0][f[2]].astype(np.int64)
        return set(((c[:, 0] * 64 + c[:, 1]) * 64 + c[:, 2]).tolist())

    k0, k1 = keys(f0), keys(f1)
    assert 0.9 < len(k0 & k1) / max(len(k0), len(k1)) < 1.0
    assert not np.array_equal(s.object_origin(0), s.object_origin(1))
