"""The reference's building blocks against a dense 3D conv, for the kernel
sizes and strides that an architecture may use."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as ref

RES = 8


def _grid(seed: int, n: int, c_in: int):
    """``n`` distinct active voxels of an 8^3 grid and their features."""
    rng = np.random.default_rng(seed)
    flat = rng.choice(RES ** 3, size=n, replace=False)
    coords = np.stack([flat // RES ** 2, (flat // RES) % RES, flat % RES], 1)
    return coords, rng.standard_normal((n, c_in)).astype(np.float32)


def _dense_conv(coords, feats, w, b, out_coords, size, stride, centered):
    """out[o] = sum_d dense[stride o + d] @ w[d] + b over a zero-padded
    dense grid, read at the output voxels: a plain 3D conv."""
    dense = np.zeros((RES, RES, RES, feats.shape[1]))
    dense[tuple(coords.T)] = feats
    pad = size
    padded = np.pad(dense, [(pad, pad)] * 3 + [(0, 0)])
    out = np.zeros((len(out_coords), w.shape[-1]))
    for k, d in enumerate(ref.offsets(size, centered)):
        p = stride * out_coords + d + pad
        out += padded[p[:, 0], p[:, 1], p[:, 2]] @ w[k]
    return out + b


@pytest.mark.parametrize("size,stride,centered", [
    (5, 1, True),     # a 5^3 stem: 125 planes
    (1, 1, True),     # a 1x1 projection
    (3, 1, True),     # a submanifold 3^3 conv
    (2, 2, False),    # a strided 2^3 conv down
])
def test_masked_conv_is_a_dense_conv_on_the_active_voxels(size, stride,
                                                          centered):
    coords, feats = _grid(size * 10 + stride, 90, 3)
    if stride == 1:
        out_coords = coords
    else:
        out_coords = ref.coarsen(coords, stride, RES // stride)
    rng = np.random.default_rng(1)
    w = rng.standard_normal((size ** 3, 3, 4)).astype(np.float32)
    b = rng.standard_normal(4).astype(np.float32)
    nbr = ref.table(ref.Index(coords, RES), out_coords, size, stride,
                    centered)
    assert nbr.shape == (len(out_coords), size ** 3)
    cap = 128
    with jax.default_matmul_precision("highest"):
        got = ref.conv(jnp.asarray(ref.pad_rows(feats, cap, 0.0)),
                       ref.pad_rows(nbr, cap, -1), w, b,
                       ref.pad_rows(np.ones(len(out_coords), bool), cap,
                                    False), None)
    got = np.asarray(got)
    want = _dense_conv(coords, feats, w, b, out_coords, size, stride,
                       centered)
    np.testing.assert_allclose(got[:len(out_coords)], want, rtol=1e-5,
                               atol=1e-5)
    assert not got[len(out_coords):].any()


def test_transposed_conv_is_a_dense_scatter():
    """Each fine voxel reads its coarse parent through the plane of its
    position inside the parent: a stride-2 transposed conv."""
    fine, _ = _grid(7, 90, 1)
    coarse = ref.coarsen(fine, 2, RES // 2)
    x = np.random.default_rng(2).standard_normal(
        (len(coarse), 3)).astype(np.float32)
    w = np.random.default_rng(3).standard_normal((8, 3, 4)).astype(np.float32)
    src, plane = ref.up_table(ref.Index(coarse, RES // 2), fine, 2)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.up(jnp.asarray(x), src, plane, w,
                                np.zeros(4, np.float32),
                                np.ones(len(fine), bool), None))
    offs = [tuple(d) for d in ref.offsets(2, False)]
    parent = {tuple(c): i for i, c in enumerate(coarse)}
    for o, c in enumerate(fine):
        want = x[parent[tuple(c // 2)]] @ w[offs.index(tuple(c % 2))]
        np.testing.assert_allclose(got[o], want, rtol=1e-5, atol=1e-5)


def test_batch_norm_over_the_active_rows():
    """Normalised by the active rows' mean and biased variance, then scaled
    and offset; inactive rows read zero, with and without the ReLU."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((16, 3)).astype(np.float32) * 3 + 1
    mask = np.arange(16) < 11
    scale, offset = np.float32([1.0, 2.0, 0.5]), np.float32([0.0, 1.0, -1])
    y = np.asarray(ref.batch_norm(jnp.asarray(x), mask, scale, offset))
    a = x[mask].astype(np.float64)
    want = (a - a.mean(0)) / np.sqrt(a.var(0) + 1e-5) * scale + offset
    np.testing.assert_allclose(y[mask], want, rtol=1e-5, atol=1e-5)
    assert not y[~mask].any()
    r = np.asarray(ref.bn_relu(jnp.asarray(x), mask, scale, offset))
    np.testing.assert_array_equal(r, np.maximum(y, 0))
