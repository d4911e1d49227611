"""The MinkUNet cell runs from its added files, and its check catches a
wrong MinkUNet: no residual add, a 3^3 stem in place of the 5^3, SCN's
[skip, up] concat order."""
import json
import shutil

import numpy as np
import pytest

import plug
import run

CELL = "mink34c.rooms2cm"
#: MinkUNet34C's shape at widths a CPU holds: 3 levels, projections where
#: a stage widens, a 5^3 stem on a 32^3 grid
TINY_MINK = {"init_dim": 8, "planes": [16, 24, 24, 16],
             "layers": [2, 1, 1, 2], "full_scale": 32, "capacity": 2048}
TINY_ROOMS = {"voxels": {"median": 700, "sigma": 0.3, "lo": 400, "hi": 900},
              "pin": {"rooms": 1, "voxels": 900, "objects": 8},
              "warm_voxels": 300, "rate_per_s": 4}


@pytest.fixture(scope="module")
def mink_root(tmp_path_factory):
    """A checkout holding BENCHMARK.json and bench/, whose MinkUNet config
    and 2 cm mix are cut to the CPU's sizes."""
    root = tmp_path_factory.mktemp("mink")
    shutil.copytree(run.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(run.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    cell = run.load_cell(CELL)
    for sub, name, small in (("configs", cell["config"], TINY_MINK),
                             ("traffic", cell["traffic"], TINY_ROOMS)):
        path = root / "bench" / sub / f"{name}.json"
        data = json.loads(path.read_text())
        data.update(small)
        path.write_text(json.dumps(data))
    return root


def test_the_cell_runs_from_its_files_and_is_correct(mink_root, cpu_devices):
    session = {}
    res = run.run_cell(CELL, 3_000_000_017, 1.5, False, root=mink_root,
                       devices=cpu_devices, session=session)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"scenes_per_s", "setup_s"}
    assert session["model"].__file__.endswith("archs/minkunet.py")
    assert session["program"].__file__.endswith("archs/minkunet_sut.py")


def test_every_per_layer_metric_reads_in_the_cell(mink_root, cpu_devices):
    """As for the SCN cells: each per-layer metric the cell declares finds
    something to read (the trace's numbers are made up: the CPU's trace
    has no TPU plane)."""
    import work

    session = {}
    run.run_cell(CELL, 5, 1.5, False, root=mink_root, devices=cpu_devices,
                 session=session)
    win, cfg, model = session["window"], session["cfg"], session["model"]
    done = [r for r in win.records if r.status == "completed"]
    pk = work.peaks("TPU v5 lite")
    cell = run.load_cell(CELL, mink_root)
    assert {m["name"] for m in cell["per_layer"]} == {
        f"{base}.rooms2cm" for base in (
            "plan_stem_ms_per_scene", "plan_ms_per_scene",
            "plan_wait_ms_per_wave", "unet_device_ms_per_scene", "unet_mfu",
            "sspnna_ms_per_scene", "sspnna_roofline", "idle_pct",
            "idle_plan_wait_pct")}
    ctx = {"window": win, "done": done,
           "trace": {"busy_s": 0.5, "window_s": 2.0,
                     "kernel_s": {"sspnna": 0.1}, "idle_plan_wait_pct": 97.0},
           "chips": 1, "peaks": pk,
           "work": work.window_work(
               session["traffic"], done, cfg, model,
               session["program"].kernel_sites(
                   next(iter(session["specs"].values()))), pk)}
    for m in cell["per_layer"]:
        v = run.reader(m["name"], mink_root)(ctx)
        assert v is not None and v >= 0, m["name"]


def test_the_work_counts_every_conv_once():
    """MinkUNet34C's published shape at full widths: 37.86 M parameters
    with 4 input features, and per scene the stem, two 3^3 convs a block,
    a projection where a stage widens, four downs and ups, the head."""
    cfg = run.load_cell(CELL)["cfg"]
    model = plug.arch(cfg, run.ROOT)
    shapes = model.weight_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 37_860_052
    rng = np.random.default_rng(0)
    coords = np.unique(rng.integers(0, 64, (3000, 3)), axis=0)
    sites = [c[:2] for c in model.scene_convs(coords, cfg)]
    blocks = sum(cfg["layers"])
    assert sites.count(("stem", 0)) == 1
    assert sum(s == "sub" for s, _ in sites) == 2 * blocks
    assert sum(s == "proj" for s, _ in sites) == 7
    assert sum(s in ("down", "up") for s, _ in sites) == 8
    assert [s for s, _ in sites].count("head") == 1


def _no_residual_add(eng, mp):
    """Plant a fault in the blocks: the shortcut is never added."""
    import jax.numpy as jnp

    from repro.engine import api

    def block(x, mask, plan, p, **kw):
        y = api._bn(api.sparse_conv(x, p["conv1"], plan, **kw), mask,
                    p["bn1"])
        y = api._bn(api.sparse_conv(y, p["conv2"], plan, **kw), mask,
                    p["bn2"], relu=False)
        return jnp.maximum(y, 0)

    mp.setattr(api, "residual_block", block)


def _stem_3cube(eng, mp):
    """Plant a fault in the stem: a 3^3 stem on level 0's own plan, with
    the 27 central planes of the 5^3 weights."""
    from repro.core.hashgrid import kernel_offsets
    from repro.core.sparse_conv import SparseConvParams

    offs = kernel_offsets(5)
    central = np.flatnonzero(np.abs(offs).max(axis=1) <= 1)
    stem = eng.params["stem"]
    eng.params["stem"] = SparseConvParams(stem.weight[central], stem.bias)
    plan = eng.scheduler._plan

    def plan_3cube(req):
        key, host = plan(req)
        levels = (host.levels[0]._replace(stem=None),) + host.levels[1:]
        return key + "|3cube", type(host)(levels, host.stats)

    eng.scheduler._plan = plan_3cube


def _skip_up_concat(eng, mp):
    """Plant a fault in the decoder: SCN's [skip, up] order, with the
    widths of MinkUNet's [up, skip]."""
    from repro.engine import api

    walk = api.apply_unet

    def apply_skip_up(*args, **kw):
        kw["concat"] = "skip_up"
        return walk(*args, **kw)

    mp.setattr(api, "apply_unet", apply_skip_up)


@pytest.mark.parametrize("fault", [_no_residual_add, _stem_3cube,
                                   _skip_up_concat],
                         ids=["no_residual_add", "stem_3cube",
                              "skip_up_concat"])
def test_a_planted_fault_is_not_correct(mink_root, cpu_devices, fault):
    good = run.run_cell(CELL, 9, 1.5, False, root=mink_root,
                        devices=cpu_devices)
    assert good["correct"]
    with pytest.MonkeyPatch.context() as mp:
        bad = run.run_cell(CELL, 9, 1.5, False, root=mink_root,
                           devices=cpu_devices,
                           on_engine=lambda eng: fault(eng, mp))
    assert not bad["correct"] and bad["failed"] == 0
