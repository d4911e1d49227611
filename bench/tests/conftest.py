"""The benchmark's own tests: on the CPU, at sizes a test run can hold.

    python -m pytest bench/tests

They sit outside the repo's ``testpaths``.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

#: a U-Net and traffic small enough for the CPU: the published depth is
#: cut to 3 levels and the grid to 32^3
TINY_CFG = {"name": "tiny", "n_planes": [8, 16, 24], "full_scale": 32,
            "capacity": 2048}
TINY_ROOMS = {"voxels": {"median": 700, "sigma": 0.3, "lo": 400, "hi": 900},
              "pin": {"rooms": 1, "voxels": 900, "objects": 8},
              "warm_voxels": 300, "rate_per_s": 4}
TINY_STREAM = {"room_voxels": 800, "object_voxels": 150,
               "pin": {"rooms": 2, "voxels": 900, "objects": 8}}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout holding only BENCHMARK.json and bench/, whose cells run
    a tiny config, plus a stream cell; its traffic mixes keep their shapes
    at smaller sizes."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "scn_scannet_m16.json").read_text())
    cfg.update(TINY_CFG)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    for mix, small in (("rooms", TINY_ROOMS), ("fixed_sensor", TINY_STREAM)):
        path = root / "bench" / "traffic" / f"{mix}.json"
        m = json.loads(path.read_text())
        m.update(small)
        path.write_text(json.dumps(m))
    for w in bench["workloads"]:
        w["config"] = "tiny"
    # the fixed-sensor mix has no cell on the chip yet; this one drives it
    bench["workloads"].append({"name": "tiny.stream", "config": "tiny",
                               "traffic": "fixed_sensor", "chips": 1,
                               "why": "patched stream plans"})
    bench["end_to_end"].append({"name": "frames_per_s", "unit": "frames/s",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny.stream"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    return jax.devices()
