"""FLOPs and compulsory bytes, against a count by hand."""
import numpy as np

import plug
import run
import work

SCN = plug.arch({"arch": "scn_unet"}, run.ROOT)


def test_pairs_and_work_by_hand():
    # two voxels side by side in x, one alone; level 1 merges the pair
    coords = np.array([[0, 0, 0], [1, 0, 0], [6, 6, 6]])
    geo = SCN.geometry(coords, 2, 8)
    pc = SCN.pair_counts(geo)
    # level 0: each voxel is its own centre neighbour, the pair sees each
    # other once more: 3 + 2 pairs; level 1 holds (0,0,0) and (3,3,3)
    assert pc == {"n": [3, 2], "sub": [5, 2], "down": [3], "up": [3]}
    cfg = {"n_planes": [2, 4], "full_scale": 8, "block_reps": 1,
           "input_features": 3, "nClasses": 5}
    convs = SCN.scene_convs(coords, cfg)
    assert convs == SCN.convs(pc, [2, 4], 1, 3, 5)
    assert [c[:2] for c in convs] == [("stem", 0), ("sub", 0), ("down", 0),
                                      ("up", 0), ("sub", 0), ("sub", 1),
                                      ("head", 0)]
    stem = convs[0]
    assert stem[2] == 2 * 5 * 3 * 2                      # pairs x Cin x Cout
    # in, weights, out: bfloat16 values
    assert stem[3] == 2 * (3 * 3 + 27 * 3 * 2 + 3 * 2)
    down = convs[2]
    assert down[2] == 2 * 3 * 2 * 4
    assert down[3] == 2 * (3 * 2 + 8 * 2 * 4 + 2 * 4)
    dec = convs[4]                                       # concat: 2 x width
    assert dec[2] == 2 * 5 * 4 * 2
    assert convs[-1][2] == 2 * 3 * 2 * 5
    # the classifier as a 1x1 conv: rows in, weights, logits out
    assert convs[-1][3] == 2 * (3 * 2 + 2 * 5 + 3 * 5)


def test_kernel_calls_follow_the_kernel_levels():
    coords = np.array([[0, 0, 0], [1, 0, 0], [6, 6, 6]])
    cfg = {"n_planes": [2, 4], "full_scale": 8, "block_reps": 1,
           "input_features": 3, "nClasses": 5}
    sites0 = {("stem", 0), ("sub", 0)}
    none = work.scene_work(coords, cfg, SCN, set())
    lvl0 = work.scene_work(coords, cfg, SCN, sites0)
    both = work.scene_work(coords, cfg, SCN, sites0 | {("sub", 1)})
    assert none["kernel"] == [] and len(lvl0["kernel"]) == 3  # stem, enc, dec
    assert len(both["kernel"]) == 4
    assert none["flops"] == both["flops"]


def test_peaks_know_the_v5e_and_nothing_else():
    pk = work.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    try:
        work.peaks("cpu")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
