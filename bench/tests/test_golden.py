"""The SCN U-Net behind the architecture seam reads as the harness read it
before: ``bench/testdata/scn_golden.json`` holds digests and counts made by
the harness before ``bench/archs/`` existed, at the tiny sizes of these
tests and, where cheap, at the configs' own: the weights of both configs
from one seed, the reference logits (and both controls) of one room, the
convs and kernel work of a room, the rooms of a window with their pinning,
warm-up and check sample, and a fixed-sensor stream's first frames."""
import hashlib
import json

import numpy as np
import pytest

import check
import drive
import plug
import run
import scenes
import weights
import work

GOLDEN = json.loads((run.BENCH / "testdata" / "scn_golden.json").read_text())
CONFIGS = ("scn_scannet_m16", "scn_scannet_m32")


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def config(name: str, size: str) -> dict:
    cfg = json.loads((run.BENCH / "configs" / f"{name}.json").read_text())
    if size == "tiny":
        cfg.update(GOLDEN["tiny_cfg"])
    return cfg


def mix(name: str, size: str) -> dict:
    m = json.loads((run.BENCH / "traffic" / f"{name}.json").read_text())
    if size == "tiny":
        m.update(GOLDEN["tiny_rooms" if name == "rooms" else "tiny_stream"])
    return m


def model(cfg: dict):
    return plug.arch(cfg, run.ROOT)


@pytest.mark.parametrize("size", ["tiny", "full"])
@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_bitwise_the_same(name, size):
    cfg = config(name, size)
    w = weights.make_weights(GOLDEN["seed"], model(cfg).weight_shapes(cfg))
    assert digest(*(w[k] for k in sorted(w))) == \
        GOLDEN["weights"][name][size]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_and_controls_are_bitwise_the_same(name):
    cfg = config(name, "tiny")
    m = model(cfg)
    w = weights.make_weights(GOLDEN["seed"], m.weight_shapes(cfg))
    c, f, _, mask = scenes.room_with_voxels(GOLDEN["room_seed"], 700, 4,
                                            cfg["full_scale"],
                                            cfg["capacity"])
    want = GOLDEN["logits"][name]
    for key, dtypes in (("reference", {}), ("control", check.CONTROL),
                        ("bf16", check.BF16)):
        got = m.logits(w, c[mask], f[mask], cfg, **dtypes)
        assert digest(got) == want[key], key


@pytest.mark.parametrize("size", ["tiny", "full"])
@pytest.mark.parametrize("name", CONFIGS)
def test_convs_and_kernel_work_are_the_same(name, size):
    cfg = config(name, size)
    m = model(cfg)
    c, _, _, mask = scenes.room_with_voxels(
        GOLDEN["room_seed"], 700 if size == "tiny" else 45000, 4,
        cfg["full_scale"], cfg["capacity"])
    want = GOLDEN["convs"][name]
    assert [list(x) for x in m.scene_convs(c[mask], cfg)] == want[size]
    sites = {("stem", 0)} | {("sub", li) for li in (0, 1, 2)}
    kw = work.scene_work(c[mask], cfg, m, sites)
    assert [list(x) for x in kw["kernel"]] == want[size + "_kernel012"]
    assert kw["flops"] == want[size + "_flops"]


@pytest.mark.parametrize("size", ["tiny", "full"])
def test_rooms_are_bitwise_the_same(size):
    cfg = config("scn_scannet_m16", size)
    m = mix("rooms", size)
    tr = drive.traffic(m, GOLDEN["seed"], 50.0, cfg)
    pins = drive.pin_rooms(m, cfg)
    want = GOLDEN["rooms"][size]
    assert len(tr.pool) == want["n"]
    assert digest(*(a for r in tr.pool for a in r)) == want["pool"]
    assert digest(*(a for r in tr.warm for a in r)) == want["warm"]
    assert digest(*(a for s in pins
                    for a in (s.coords, s.feats, s.mask))) == want["pin"]
    assert sorted(tr.sample) == want["sample"]
    assert tr.largest == want["largest"]


def test_stream_frames_are_bitwise_the_same():
    cfg = config("scn_scannet_m16", "tiny")
    st = drive.traffic(mix("fixed_sensor", "tiny"), GOLDEN["seed"], 2.0, cfg)
    want = GOLDEN["stream"]["tiny"]
    assert digest(*(a for s in st.sensors for t in range(3)
                    for a in s.frame(t))) == want["frames"]
    assert sorted([list(x) for x in st.sample]) == want["sample"]
