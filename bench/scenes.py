"""Scene generators for the benchmark's traffic: indoor rooms and fixed-sensor
streams, made on the host from a seed.

``make_room`` is the repo's ScanNet-like room distribution
(``repro.data.scenes.make_scene``: floor, two walls and box, cylinder and
sphere furniture sampled as surface points with normals, then voxelized),
copied here with the per-voxel Python loop replaced by ``np.bincount``
reductions. ``bench/tests/test_scenes.py`` holds the copy to the original:
the same coords, mask and labels, and features equal to within float32
rounding of the per-voxel means.

Nothing here imports the program: the yardstick's traffic may not change when
the program does.
"""
from __future__ import annotations

import numpy as np

N_CLASSES = 5
N_FEATURES = 4
PAD_COORD = -1
#: surface points per unit that give a room of ~50k voxels at 256^3
POINTS_PER_UNIT = 1.2e6


def _box_surface(rng, n, lo, hi):
    pts = rng.uniform(lo, hi, (n, 3))
    face = rng.integers(0, 6, n)
    axis, side = face // 2, face % 2
    pts[np.arange(n), axis] = np.where(side == 0, lo[axis], hi[axis])
    normals = np.zeros((n, 3))
    normals[np.arange(n), axis] = np.where(side == 0, -1.0, 1.0)
    return pts, normals


def _sphere_surface(rng, n, center, radius):
    v = rng.normal(size=(n, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True) + 1e-9
    return center + radius * v, v


def _cylinder_surface(rng, n, center, radius, height):
    theta = rng.uniform(0, 2 * np.pi, n)
    z = rng.uniform(0, height, n)
    pts = np.stack(
        [center[0] + radius * np.cos(theta), center[1] + radius * np.sin(theta),
         center[2] + z], axis=1)
    normals = np.stack([np.cos(theta), np.sin(theta), np.zeros(n)], axis=1)
    return pts, normals


def room_points(seed: int, points_per_unit: float, n_objects: int):
    """Surface samples of one room -> (points (P,3), feats (P,4) f32,
    labels (P,) int32); the draw order of ``make_scene``."""
    rng = np.random.default_rng(seed)
    pts_list, nrm_list, lbl_list = [], [], []

    def add(pts, normals, label):
        pts_list.append(pts)
        nrm_list.append(normals)
        lbl_list.append(np.full(len(pts), label, np.int32))

    nf = int(points_per_unit * 0.015)
    floor = np.stack(
        [rng.uniform(0.02, 0.98, nf), rng.uniform(0.02, 0.98, nf),
         np.full(nf, 0.03) + rng.normal(0, 0.002, nf)], axis=1)
    add(floor, np.tile([0.0, 0.0, 1.0], (nf, 1)), 0)
    for wall_axis in (0, 1):
        nw = int(points_per_unit * 0.01)
        w = np.stack(
            [rng.uniform(0.02, 0.98, nw), rng.uniform(0.02, 0.98, nw),
             rng.uniform(0.03, 0.7, nw)], axis=1)
        w[:, wall_axis] = 0.03 + rng.normal(0, 0.002, nw)
        nrm = np.zeros((nw, 3))
        nrm[:, wall_axis] = 1.0
        add(w, nrm, 1)
    for _ in range(n_objects):
        kind = rng.integers(2, 5)
        npts = int(points_per_unit * 0.004)
        cx, cy = rng.uniform(0.2, 0.8, 2)
        if kind == 2:
            size = rng.uniform(0.06, 0.18, 3)
            lo = np.array([cx, cy, 0.03])
            pts, nrm = _box_surface(rng, npts, lo, lo + size)
        elif kind == 3:
            pts, nrm = _cylinder_surface(
                rng, npts, np.array([cx, cy, 0.03]),
                rng.uniform(0.03, 0.08), rng.uniform(0.1, 0.3))
        else:
            r = rng.uniform(0.04, 0.1)
            pts, nrm = _sphere_surface(rng, npts, np.array([cx, cy, 0.03 + r]),
                                       r)
        add(pts, nrm, int(kind))
    pts = np.clip(np.concatenate(pts_list), 0.0, 0.999)
    nrm = np.concatenate(nrm_list)
    lbl = np.concatenate(lbl_list)
    feats = np.concatenate([nrm, pts[:, 2:3]], axis=1).astype(np.float32)
    return pts, feats, lbl


def voxelize(pts, feats, lbl, resolution: int, capacity: int):
    """Points -> padded voxels sorted by linear key: per-voxel mean feature
    and majority label (ties to the lower class)."""
    ijk = np.clip((pts * resolution).astype(np.int64), 0, resolution - 1)
    key = (ijk[:, 0] * resolution + ijk[:, 1]) * resolution + ijk[:, 2]
    uniq, inv, counts = np.unique(key, return_inverse=True,
                                  return_counts=True)
    n = min(len(uniq), capacity)
    sums = np.stack([np.bincount(inv, weights=feats[:, c],
                                 minlength=len(uniq))
                     for c in range(feats.shape[1])], axis=1)
    votes = np.bincount(inv * N_CLASSES + lbl,
                        minlength=len(uniq) * N_CLASSES)
    coords = np.full((capacity, 3), PAD_COORD, np.int32)
    out_feats = np.zeros((capacity, feats.shape[1]), np.float32)
    out_lbl = np.zeros((capacity,), np.int32)
    mask = np.zeros((capacity,), bool)
    coords[:n, 0] = uniq[:n] // (resolution * resolution)
    coords[:n, 1] = (uniq[:n] // resolution) % resolution
    coords[:n, 2] = uniq[:n] % resolution
    out_feats[:n] = (sums[:n] / counts[:n, None]).astype(np.float32)
    out_lbl[:n] = votes.reshape(-1, N_CLASSES)[:n].argmax(1)
    mask[:n] = True
    return coords, out_feats, out_lbl, mask


def make_room(seed: int, resolution: int, capacity: int,
              points_per_unit: float = 60000.0, n_objects: int = 4):
    """Vectorized ``repro.data.scenes.make_scene``: the same signature and
    result -> coords (V,3) int32, feats (V,4) f32, labels (V,) int32,
    mask (V,) bool."""
    pts, feats, lbl = room_points(seed, points_per_unit, n_objects)
    return voxelize(pts, feats, lbl, resolution, capacity)


def _seq(seed, *more) -> list:
    """A seed (an int or a list of ints) extended by ``more``."""
    return (list(seed) if isinstance(seed, (list, tuple)) else [seed]) + \
        list(more)


def _stratified(n: int, rng, lo: float, hi: float) -> np.ndarray:
    """n points of (lo, hi), one in each of n equal strata, in seeded
    order: every seed draws the same spread of values."""
    u = (np.arange(n) + 0.5) / n
    return lo + (hi - lo) * rng.permutation(u)


def room_sizes(n: int, seed: int, median: float, sigma: float, lo: int,
               hi: int, obj_lo: int, obj_hi: int):
    """Target voxel counts and object counts of ``n`` rooms.

    The counts are the lognormal's stratified quantiles, clipped to
    [lo, hi], and the object counts are spread evenly over [obj_lo, obj_hi];
    the seed only orders them. So every seed serves the same amount of work
    and the seed changes which room comes when, not how much there is."""
    from statistics import NormalDist

    rng = np.random.default_rng(_seq(seed, 1))
    q = _stratified(n, rng, 0.0, 1.0)
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    voxels = np.clip(median * np.exp(sigma * z), lo, hi).astype(np.int64)
    objects = obj_lo + (np.arange(n) % (obj_hi - obj_lo + 1))
    return voxels, rng.permutation(objects)


def room_with_voxels(seed: int, target: int, n_objects: int, resolution: int,
                     capacity: int):
    """A room whose active voxel count is near ``target``: the point density
    is set from a first draw's count (voxels grow sub-linearly in points),
    then the room is drawn again at that density from the same seed."""
    ppu = POINTS_PER_UNIT * target / 50_000
    for _ in range(3):
        pts, feats, lbl = room_points(seed, ppu, n_objects)
        coords, f, lab, mask = voxelize(pts, feats, lbl, resolution, capacity)
        got = int(mask.sum())
        if abs(got - target) <= 0.02 * target:
            break
        # distinct voxels ~ points^0.8 in this range of density
        ppu *= (target / max(got, 1)) ** 1.25
    return coords, f, lab, mask


class FixedSensorStream:
    """Frames of one fixed sensor watching one room.

    The room's static voxels come from ``make_room``'s distribution; one box
    of ``object_voxels`` surface voxels moves by one voxel per frame along a
    seeded direction, bouncing off the room's bounds; each frame drops
    ``dropout`` of all voxels at random. The sensor does not move, so every
    frame's ego shift is (0, 0, 0). Frame ``t`` is a function of
    ``(seed, t)`` alone: any run can regenerate any frame.
    """

    def __init__(self, seed: int, resolution: int, capacity: int, *,
                 room_voxels: int, n_objects: int, object_voxels: int,
                 dropout: float):
        self.seed = seed
        self.resolution = resolution
        self.capacity = capacity
        self.dropout = dropout
        coords, feats, _, mask = room_with_voxels(
            seed, room_voxels - object_voxels, n_objects, resolution,
            capacity)
        n = int(mask.sum())
        self.static_coords = coords[:n].astype(np.int64)
        self.static_feats = feats[:n]
        rng = np.random.default_rng(_seq(seed, 2))
        # an axis-aligned box whose surface holds ~object_voxels voxels:
        # 6 faces of side s hold ~6 s^2 voxels
        side = max(2, int(round(np.sqrt(object_voxels / 6.0))))
        box = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                       -1).reshape(-1, 3)
        shell = np.any((box == 0) | (box == side - 1), axis=1)
        self.obj_coords = box[shell]
        normals = np.zeros((len(self.obj_coords), 3), np.float32)
        for ax in range(3):
            normals[self.obj_coords[:, ax] == 0, ax] = -1.0
            normals[self.obj_coords[:, ax] == side - 1, ax] = 1.0
        self.obj_normals = normals / np.maximum(
            np.linalg.norm(normals, axis=1, keepdims=True), 1.0)
        margin = side + 2
        self.lo = np.array([margin, margin, 2])
        self.hi = np.array([resolution - 2 * margin] * 2
                           + [resolution // 3])
        self.start = rng.integers(self.lo, self.hi)
        self.direction = rng.choice([-1, 1], size=3) * (
            np.arange(3) == rng.integers(0, 2))

    def object_origin(self, t: int) -> np.ndarray:
        """Box corner at frame ``t``: one voxel a frame, reflected at the
        bounds."""
        span = self.hi - self.lo
        pos = self.start - self.lo + self.direction * t
        period = np.maximum(2 * span, 1)
        pos = np.mod(pos, period)
        pos = np.where(pos > span, period - pos, pos)
        return self.lo + pos

    def frame(self, t: int):
        """-> coords (V,3) int32, feats (V,4) f32, mask (V,) bool, with the
        active rows first, sorted by linear key."""
        r = self.resolution
        obj = self.obj_coords + self.object_origin(t)
        obj_feats = np.concatenate(
            [self.obj_normals, (obj[:, 2:3] + 0.5) / r], axis=1)
        coords = np.concatenate([self.static_coords, obj])
        feats = np.concatenate([self.static_feats,
                                obj_feats.astype(np.float32)])
        key = (coords[:, 0] * r + coords[:, 1]) * r + coords[:, 2]
        key, first = np.unique(key, return_index=True)  # object over room
        coords, feats = coords[first], feats[first]
        rng = np.random.default_rng(_seq(self.seed, 3, t))
        keep = rng.random(len(key)) >= self.dropout
        coords, feats = coords[keep], feats[keep]
        n = min(len(coords), self.capacity)
        out_c = np.full((self.capacity, 3), PAD_COORD, np.int32)
        out_f = np.zeros((self.capacity, N_FEATURES), np.float32)
        mask = np.zeros((self.capacity,), bool)
        out_c[:n] = coords[:n]
        out_f[:n] = feats[:n]
        mask[:n] = True
        return out_c, out_f, mask
