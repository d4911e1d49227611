"""The harness is driven by data, fails without a chip, and its check
catches a wrong answer and the control."""
import json
import shutil

import numpy as np
import pytest

import run


def test_no_tpu_no_result(capsys):
    code = run.main(["--workload", "m16.rooms", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0
    assert "no TPU" in out.err
    assert not out.out.strip()


def test_every_cell_resolves_to_its_files():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["cfg"]["name"] == w["config"]
        assert cell["mix"]["kind"] in ("rooms", "stream")
        archs = run.ROOT / "bench" / "archs"
        for side in ("", "_sut"):
            assert (archs / f"{cell['cfg']['arch']}{side}.py").is_file()
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(run.reader(m["name"]))


def test_a_new_cell_runs_from_added_files(tiny_root, tmp_path, cpu_devices):
    """A config, a mix, a workload entry and an architecture are added as
    files; no file of the harness changes. The architecture is the SCN
    U-Net's two modules under another name, which only the new config
    names."""
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    archs = root / "bench" / "archs"
    for side in ("", "_sut"):
        shutil.copy(archs / f"scn_unet{side}.py",
                    archs / f"scn_copy{side}.py")
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg.update(name="tiny_reps2", block_reps=2, arch="scn_copy")
    (root / "bench/configs/tiny_reps2.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/rooms.json").read_text())
    mix.update(outstanding=2, objects=[3, 5])
    (root / "bench/traffic/rooms_pairs.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny2.pairs", "config": "tiny_reps2",
                               "traffic": "rooms_pairs", "chips": 1,
                               "why": "a cell added by data alone"})
    for m in bench["end_to_end"]:
        if "scenes_per_s" == m["name"]:
            m["workloads"].append("tiny2.pairs")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    session = {}
    res = run.run_cell("tiny2.pairs", 77, 2.0, False, root=root,
                       devices=cpu_devices, session=session)
    assert res["correct"] and res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"scenes_per_s", "setup_s"}
    assert list(res)[-1] == "check"
    assert all(before[p] == p.read_bytes() for p in before)
    assert session["model"].__file__ == str(archs / "scn_copy.py")
    assert session["program"].__file__ == str(archs / "scn_copy_sut.py")


@pytest.mark.parametrize("cell", ["m16.rooms", "m32.rooms"])
def test_every_per_layer_metric_reads_in_its_cells(tiny_root, cpu_devices,
                                                   cell):
    """Each per-layer metric a cell declares finds something to read there
    (a traced run whose line lacks one is refused). The trace's numbers are
    made up: the CPU's trace has no TPU plane."""
    import work

    session = {}
    run.run_cell(cell, 3, 1.5, False, root=tiny_root, devices=cpu_devices,
                 session=session)
    win, cfg, model = session["window"], session["cfg"], session["model"]
    done = [r for r in win.records if r.status == "completed"]
    pk = work.peaks("TPU v5 lite")
    cell_ = run.load_cell(cell, tiny_root)
    ctx = {"window": win, "done": done,
           "trace": {"busy_s": 0.5, "window_s": 2.0,
                     "kernel_s": {"sspnna": 0.1}, "idle_plan_wait_pct": 97.0},
           "chips": 1, "peaks": pk,
           "work": work.window_work(session["traffic"], done, cfg, model,
                                    {("stem", 0), ("sub", 0)}, pk)}
    for m in cell_["per_layer"]:
        v = run.reader(m["name"], tiny_root)(ctx)
        assert v is not None and v >= 0, m["name"]


def _alter_answers(eng):
    """Plant a fault where answers are produced: one voxel's logits of
    every request come out wrong."""
    drain = eng.scheduler._drain

    def drain_and_alter(reqs, handle):
        drain(reqs, handle)
        for r in reqs:
            r.logits = np.array(r.logits)
            r.logits[0] += 1.0

    eng.scheduler._drain = drain_and_alter


def _stale_plans(eng):
    """Plant a fault in the plan step: every request after the first is
    served with the first request's plan (a plan state that never moves
    on), each with its own features."""
    plan = eng.scheduler._plan
    first = {}

    def plan_stale(req):
        payload = plan(req)
        stream = payload[0] == "stream"
        old = first.setdefault(stream, payload)
        return payload[:2] + (old[2],) + payload[3:] if stream else old

    eng.scheduler._plan = plan_stale


@pytest.mark.parametrize("cell", ["m16.rooms", "tiny.stream"])
@pytest.mark.parametrize("fault", [_alter_answers, _stale_plans],
                         ids=["altered_answer", "stale_plan"])
def test_a_planted_fault_is_not_correct(tiny_root, cpu_devices, cell, fault):
    good = run.run_cell(cell, 5, 1.5, False, root=tiny_root,
                        devices=cpu_devices)
    assert good["correct"]
    bad = run.run_cell(cell, 5, 1.5, False, root=tiny_root,
                       devices=cpu_devices, on_engine=fault)
    assert not bad["correct"] and bad["failed"] == 0


@pytest.mark.parametrize("cell", ["m16.rooms", "tiny.stream"])
def test_the_float8_control_is_not_correct(tiny_root, cpu_devices, cell):
    """The reference with float8 matmul operands in the program's place
    fails the limit that the program meets; the one in bfloat16 (operands
    and activations) meets it, as the configuration's stated precision
    says it may."""
    import calibrate
    import check

    session = {}
    res = run.run_cell(cell, 9, 1.5, False, root=tiny_root,
                       devices=cpu_devices, session=session)
    assert res["correct"]
    limit = session["cfg"]["check"]["limits"]["gap"]
    assert calibrate.control_readings(session, check.CONTROL)["gap"] > limit
    assert calibrate.control_readings(session, check.BF16)["gap"] < limit
