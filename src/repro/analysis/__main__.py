"""``python -m repro.analysis`` — run every static-analysis pass.

Passes (select with ``--only`` / drop with ``--skip``):

* ``lint``  — AST rules over src/repro, examples/, benchmarks/.
* ``locks`` — lock-graph extraction + order check over src/repro.
* ``plans`` — build canonical plans (batched spec'd, sharded, streaming)
  from a small synthetic scene and run every structural invariant.
* ``hlo``   — compile the fused SSpNNA kernel on a real tile plan and run
  the forbidden-op / VMEM / recompile gates.

Exit status is the number of findings (0 = clean, capped at 125).
``--json`` additionally writes findings + the extracted lock graph.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from repro.analysis.findings import Finding, render

PASSES = ("lint", "locks", "plans", "hlo")


def find_root(start: Path | None = None) -> Path:
    """Repo root: the nearest ancestor holding ``src/repro`` (falls back
    to the package's own checkout layout)."""
    cands = [start] if start else []
    cands += [Path.cwd(), Path(__file__).resolve().parents[3]]
    for c in cands:
        if c is not None and (c / "src" / "repro").is_dir():
            return c
    raise SystemExit("cannot locate repo root (need a src/repro dir); "
                     "pass --root")


def run_lint(root: Path) -> list[Finding]:
    from repro.analysis.lint import lint_repo
    return lint_repo(root)


def run_locks(root: Path):
    from repro.analysis.concurrency import extract
    return extract(root)


def _canonical_scene(seed: int = 0, resolution: int = 16,
                     capacity: int = 512):
    import jax.numpy as jnp

    from repro.data.scenes import make_scene
    from repro.sparse.tensor import SparseVoxelTensor
    coords, feats, _, mask = make_scene(seed, resolution=resolution,
                                        capacity=capacity)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def run_plans(root: Path) -> list[Finding]:
    """Build one plan of each kind from a canonical synthetic scene and
    validate every structural invariant, plus the cache-key rotations."""
    del root
    from repro import engine
    from repro.analysis.plan_check import (
        check_cache_keys,
        check_scene_plan,
        check_sharded_scene_plan,
        check_stream_state,
    )
    from repro.data.scenes import N_CLASSES
    from repro.engine.backends import BreakerBoard, default_registry
    from repro.engine.plan import PlanCache, StreamPlanState
    from repro.engine.shard import ShardLayout
    from repro.models.scn import UNetConfig

    res, cap = 16, 512
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=res, capacity=cap,
                     n_classes=N_CLASSES)
    t = _canonical_scene(0, res, cap)
    out: list[Finding] = []

    # batched, SPADE-planned with tile tables (the fused-kernel shape)
    spec = engine.build_plan_spec([t], cfg, mem_budget=64 * 1024)
    plan = engine.build_scene_plan_host(t, cfg, spec=spec, plan_tiles=True)
    out.extend(check_scene_plan(plan, "scene_plan"))

    # reference-dispatch plan (no tiles) exercises the COIR-only checks
    ref = engine.build_scene_plan_host(t, cfg, plan_tiles=False)
    out.extend(check_scene_plan(ref, "reference_plan"))

    # sharded plan with halo send tables
    layout = ShardLayout(n_shards=2, halo=256)
    splan = engine.build_sharded_scene_plan_host(t, cfg, layout=layout)
    out.extend(check_sharded_scene_plan(splan, "sharded_plan"))

    # streaming: frame 0 rebuild, frame 1 patched under an ego shift
    state = StreamPlanState(cfg, spec=spec, wait_s=30.0)
    state.plan_frame(t, 0)
    state.plan_frame(t, 1, ego_shift=(1, 0, 0))
    out.extend(check_stream_state(state, "stream"))

    # cache keys must rotate with version/topology/breaker generation
    cache = PlanCache(capacity=cap)
    out.extend(check_cache_keys(
        cache, t, cfg, breakers=BreakerBoard(default_registry())))
    return out


def run_hlo(root: Path) -> list[Finding]:
    """Compile the fused SSpNNA path on a real budgeted tile plan from the
    canonical scene; gate forbidden ops, VMEM, and the compile count."""
    del root
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.hlo_gates import (
        compiled_text,
        forbidden_ops,
        gate_compile_budget,
        gate_vmem_budget,
    )
    from repro.core import soar
    from repro.core.hashgrid import build_neighbor_table, kernel_offsets
    from repro.core.sparse_conv import submanifold_coir
    from repro.core.tiles import build_tile_plan, dma_tile_tables
    from repro.kernels.sspnna.ops import run_sspnna_conv

    res = 16
    t = _canonical_scene(0, res, 512)
    coir = submanifold_coir(t, res, 3)
    nbr = np.asarray(build_neighbor_table(
        t.coords, t.mask, jnp.asarray(kernel_offsets(3)), res))
    order = soar.soar_order(nbr, np.asarray(t.mask), 128).order
    tp = build_tile_plan(np.asarray(coir.indices), order, 16, 48)
    dma = dma_tile_tables(tp, t.capacity)
    rng = np.random.default_rng(0)
    c_in, c_out = 8, 8
    feats = jnp.asarray(rng.normal(size=(t.capacity, c_in)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(27, c_in, c_out)) * 0.1, jnp.float32)
    orow, irow = jnp.asarray(dma.out_rows), jnp.asarray(dma.in_rows)
    li, pc = jnp.asarray(tp.local_idx), jnp.asarray(dma.pair_counts)

    def fused(f, ww):
        return run_sspnna_conv(f, ww, orow, irow, li, n_out=t.capacity,
                               pair_counts=pc, use_kernel=True)

    jit = jax.jit(fused)
    out = forbidden_ops(compiled_text(jit, feats, w), where="sspnna_fused")
    out.extend(gate_compile_budget(jit, 1, where="sspnna_fused"))

    class _D:
        delta_o, delta_i, block_n = 16, 48, c_out
    out.extend(gate_vmem_budget(_D, c_in, where="sspnna_fused"))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.analysis")
    ap.add_argument("--root", type=Path, default=None,
                    help="repo root (default: auto-detect)")
    ap.add_argument("--json", type=Path, default=None, metavar="PATH",
                    help="write findings + lock graph as JSON")
    ap.add_argument("--only", choices=PASSES, action="append",
                    help="run only these passes")
    ap.add_argument("--skip", choices=PASSES, action="append", default=[],
                    help="skip these passes")
    args = ap.parse_args(argv)
    root = find_root(args.root)
    selected = [p for p in (args.only or PASSES) if p not in args.skip]

    findings: list[Finding] = []
    graph_json = None
    for name in selected:
        if name == "locks":
            got, graph = run_locks(root)
            graph_json = {
                "locks": graph.locks,
                "reentrant": sorted(graph.reentrant),
                "edges": sorted(list(e) for e in graph.edges),
            }
        else:
            got = {"lint": run_lint, "plans": run_plans,
                   "hlo": run_hlo}[name](root)
        print(f"[analysis] {name}: "
              f"{'clean' if not got else f'{len(got)} finding(s)'}")
        findings.extend(got)

    if findings:
        print(render(findings), file=sys.stderr)
    if args.json is not None:
        args.json.write_text(json.dumps({
            "passes": selected,
            "n_findings": len(findings),
            "findings": [f.to_dict() for f in findings],
            "lock_graph": graph_json,
        }, indent=2) + "\n")
        print(f"[analysis] wrote {args.json}")
    return min(len(findings), 125)


if __name__ == "__main__":
    sys.exit(main())
