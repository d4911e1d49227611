"""repro.analysis: every rule class catches a seeded violation, the real
repo is clean, and the runtime lock-order asserter works in-process.

Each static rule (REPRO-L*, C*, P*, H*) gets at least one deliberately
broken input that must produce the right finding id, plus a matching clean
input that must not. The repo-wide passes double as regression guards: the
codebase itself stays violation-free.
"""
import json
import textwrap
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.analysis.concurrency as conc
import repro.analysis.hlo_gates as hg
import repro.analysis.plan_check as pc
import repro.analysis.runtime as rt
from repro.analysis.lint import lint_repo, lint_source

REPO = Path(__file__).resolve().parents[1]


class NS:
    """Ad-hoc record standing in for a plan/tile/dispatch object."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# lint (REPRO-L001..L005)
# ---------------------------------------------------------------------------

def _lint(src, rel="src/repro/mod.py"):
    return lint_source(textwrap.dedent(src), rel)


def test_l001_deprecated_shim_import():
    f = _lint("from repro.models.scn import apply_unet\n")
    assert _rules(f) == ["REPRO-L001"]


def test_l001_deprecated_shim_attribute():
    f = _lint("""
        import repro.kernels.sspnna.ops as ops
        y = ops.sspnna_conv(0)
    """)
    assert _rules(f) == ["REPRO-L001"]


def test_l001_defining_module_exempt():
    f = _lint("from repro.models.scn import apply_unet\n",
              rel="src/repro/models/scn.py")
    assert f == []


def test_l002_host_sync_in_dispatch_stage():
    f = _lint("""
        import numpy as np
        class S:
            def _dispatch_stage(self, x):
                x.block_until_ready()
                return np.asarray(x)
    """)
    assert _rules(f) == ["REPRO-L002", "REPRO-L002"]


def test_l002_outside_hot_path_is_fine():
    f = _lint("""
        import numpy as np
        def plain(x):
            return np.asarray(x)
    """)
    assert f == []


def test_l003_unnamed_non_daemon_thread():
    f = _lint("""
        import threading
        t = threading.Thread(target=print)
        ok = threading.Thread(target=print, name="w", daemon=True)
    """)
    assert _rules(f) == ["REPRO-L003", "REPRO-L003"]  # name + daemon


def test_l003_executor_needs_name_prefix():
    f = _lint("""
        from concurrent.futures import ThreadPoolExecutor
        ex = ThreadPoolExecutor(2)
    """)
    assert _rules(f) == ["REPRO-L003"]


def test_l004_contextvars_only_banned_in_serving():
    src = "import contextvars\n"
    assert _rules(lint_source(src, "src/repro/serving/mod.py")) == \
        ["REPRO-L004"]
    assert lint_source(src, "src/repro/engine/mod.py") == []


def test_l005_readback_in_timed_closure():
    f = _lint("""
        import numpy as np
        from benchmarks.common import time_fn
        r = time_fn(lambda: np.asarray(0), iters=3)
    """)
    assert _rules(f) == ["REPRO-L005"]


def test_l005_block_until_ready_is_the_correct_fence():
    f = _lint("""
        from benchmarks.common import time_fn
        r = time_fn(lambda: f(0).block_until_ready())
    """)
    assert f == []


def test_l005_resolves_local_function_closures():
    f = _lint("""
        from benchmarks.common import measure
        def step():
            return f(0).item()
        r = measure(step)
    """)
    assert _rules(f) == ["REPRO-L005"]


def test_allow_comment_suppresses():
    f = _lint("""
        import threading
        t = threading.Thread(target=print)  # analysis: allow[REPRO-L003]
    """)
    assert f == []


def test_lint_repo_is_clean():
    assert lint_repo(REPO) == []


# ---------------------------------------------------------------------------
# concurrency (REPRO-C001..C003)
# ---------------------------------------------------------------------------

def _extract(tmp_path, source, name="mod.py"):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / name).write_text(textwrap.dedent(source))
    return conc.extract(tmp_path)


def test_c001_backward_acquisition(tmp_path):
    findings, graph = _extract(tmp_path, """
        from repro.analysis.runtime import ordered_lock
        A = ordered_lock("faults.injector")
        B = ordered_lock("plan_cache")
        def f():
            with A:
                with B:
                    pass
    """)
    assert ("faults.injector", "plan_cache") in \
        {(s, d) for s, d, _ in graph.edges}
    assert "REPRO-C001" in _rules(findings)


def test_c001_via_call_closure(tmp_path):
    findings, graph = _extract(tmp_path, """
        from repro.analysis.runtime import ordered_lock
        A = ordered_lock("plan_cache")
        B = ordered_lock("faults.injector")
        def inner():
            with B:
                pass
        def outer():
            with A:
                inner()
    """)
    # forward in rank: edge extracted through the call graph, no finding
    assert ("plan_cache", "faults.injector") in \
        {(s, d) for s, d, _ in graph.edges}
    assert findings == []


def test_c001_unknown_lock_name(tmp_path):
    findings, _ = _extract(tmp_path, """
        from repro.analysis.runtime import ordered_lock
        X = ordered_lock("not-in-the-order")
    """)
    assert "REPRO-C001" in _rules(findings)


def test_subscript_lock_defined_after_use(tmp_path):
    # the definition pass runs before the uses pass, so a dict-literal
    # lock defined *below* its acquisition site still resolves
    findings, graph = _extract(tmp_path, """
        from repro.analysis.runtime import ordered_lock
        A = ordered_lock("plan_cache")
        def use(entry):
            with A:
                with entry["dev_lock"]:
                    pass
        def make():
            return {"dev_lock": ordered_lock("plan_cache.dev")}
    """)
    assert ("plan_cache", "plan_cache.dev") in \
        {(s, d) for s, d, _ in graph.edges}
    assert findings == []


def test_c002_blocking_call_under_lock(tmp_path):
    findings, _ = _extract(tmp_path, """
        import threading
        from repro.analysis.runtime import ordered_lock
        L = ordered_lock("plan_cache")
        EV = threading.Event()
        def f():
            with L:
                EV.wait()
    """)
    assert "REPRO-C002" in _rules(findings)


def test_c002_condvar_wait_exempt(tmp_path):
    findings, _ = _extract(tmp_path, """
        from repro.analysis.runtime import ordered_condition
        C = ordered_condition("stream.plan")
        def f():
            with C:
                C.wait()
    """)
    assert findings == []


def test_c003_raw_threading_lock(tmp_path):
    findings, _ = _extract(tmp_path, """
        import threading
        L = threading.Lock()
    """)
    assert _rules(findings) == ["REPRO-C003"]


def test_repo_lock_graph_is_clean_and_live():
    findings, graph = conc.extract(REPO)
    assert findings == []
    # the extractor is not a no-op: the known stream->cache edge exists
    pairs = {(s, d) for s, d, _ in graph.edges}
    assert ("stream.plan", "plan_cache") in pairs
    assert ("stream.plan", "plan_cache.dev") in pairs  # via adopt->_resolve
    assert set(graph.locks) == set(rt.LOCK_ORDER)


# ---------------------------------------------------------------------------
# runtime lock-order asserter
# ---------------------------------------------------------------------------

def test_checked_lock_rejects_backward_acquire():
    lo = rt._CheckedLock("plan_cache")
    hi = rt._CheckedLock("faults.injector")
    with hi:
        with pytest.raises(rt.LockOrderViolation):
            lo.acquire()
    with lo:  # forward order is fine
        with hi:
            pass


def test_checked_lock_self_deadlock_and_reentrancy():
    lk = rt._CheckedLock("plan_cache")
    with lk:
        with pytest.raises(rt.LockOrderViolation):
            lk.acquire()
    r = rt._CheckedLock("breakers", reentrant=True)
    with r:
        with r:
            pass


def test_checked_lock_same_rank_distinct_objects():
    a = rt._CheckedLock("plan_cache.dev")
    b = rt._CheckedLock("plan_cache.dev")
    with a:
        with pytest.raises(rt.LockOrderViolation):
            b.acquire()


def test_checked_lock_is_per_thread():
    hi = rt._CheckedLock("faults.injector")
    lo = rt._CheckedLock("plan_cache")
    errs = []

    def other():
        try:
            with lo:
                pass
        except Exception as e:  # pragma: no cover - failure path
            errs.append(e)

    with hi:
        th = threading.Thread(target=other, name="order-test", daemon=True)
        th.start()
        th.join()
    assert errs == []


def test_factories_respect_env(monkeypatch):
    monkeypatch.delenv("REPRO_LOCK_CHECK", raising=False)
    assert not isinstance(rt.ordered_lock("plan_cache"), rt._CheckedLock)
    monkeypatch.setenv("REPRO_LOCK_CHECK", "1")
    assert isinstance(rt.ordered_lock("plan_cache"), rt._CheckedLock)
    assert isinstance(rt.ordered_rlock("breakers"), rt._CheckedLock)
    with pytest.raises(ValueError):
        rt.ordered_lock("not-a-lock")


def test_checked_condition_wait(monkeypatch):
    monkeypatch.setenv("REPRO_LOCK_CHECK", "1")
    cond = rt.ordered_condition("stream.plan")
    with cond:
        assert cond.wait(timeout=0.01) is False  # releases + re-acquires
    # after the wait round-trip the order state is intact
    with rt._CheckedLock("plan_cache"):
        pass


# ---------------------------------------------------------------------------
# plan invariants (REPRO-P001..P006)
# ---------------------------------------------------------------------------

def test_p001_coir_out_of_range():
    coir = NS(indices=np.array([[0], [5]], np.int32), bitmask=None)
    assert _rules(pc.check_coir(coir, 2, "c")) == ["REPRO-P001"]


def test_p001_bitmask_disagrees():
    idx = np.array([[0, -1], [1, 0]], np.int32)
    bad = NS(indices=idx, bitmask=np.array([3, 3], np.uint32))
    f = pc.check_coir(bad, 2, "c")
    assert _rules(f) == ["REPRO-P001"] and "bitmask" in f[0].where
    good = NS(indices=idx, bitmask=np.array([1, 3], np.uint32))
    assert pc.check_coir(good, 2, "c") == []


def _tiles(orow, irow, li, counts):
    return NS(out_rows=np.asarray(orow, np.int32),
              in_rows=np.asarray(irow, np.int32),
              local_idx=np.asarray(li, np.int32),
              pair_counts=np.asarray(counts, np.int64))


# 2 active rows, K=1, COIR row i reads input row i
_COIR2 = NS(indices=np.array([[0], [1]], np.int32), bitmask=None)
_MASK2 = np.array([True, True])


def test_tiles_clean_baseline():
    t = _tiles([[0, 1]], [[0, 1]], [[[0], [1]]], [2])
    assert pc.check_tiles(t, _COIR2, _MASK2, 2, 2, None, "t") == []


def test_p002_pair_executed_twice():
    t = _tiles([[0, 1], [0, 2]], [[0, 1], [0, 0]],
               [[[0], [1]], [[0], [-1]]], [2, 1])
    assert "REPRO-P002" in _rules(
        pc.check_tiles(t, _COIR2, _MASK2, 2, 2, None, "t"))


def test_p003_out_rows_beyond_trash():
    t = _tiles([[0, 9]], [[0, 1]], [[[0], [1]]], [2])
    assert _rules(pc.check_tiles(t, _COIR2, _MASK2, 2, 2, None, "t")) == \
        ["REPRO-P003"]


def test_p003_dispatch_mismatch():
    t = _tiles([[0, 1]], [[0, 1]], [[[0], [1]]], [2])
    d = NS(n_tiles=4, delta_o=2, delta_i=2)
    f = pc.check_tiles(t, _COIR2, _MASK2, 2, 2, d, "t")
    assert _rules(f) == ["REPRO-P003"] and "n_tiles" in f[0].message


def test_p004_pair_counts_disagree():
    t = _tiles([[0, 1]], [[0, 1]], [[[0], [1]]], [1])
    assert "REPRO-P004" in _rules(
        pc.check_tiles(t, _COIR2, _MASK2, 2, 2, None, "t"))


def test_p004_dropped_pair():
    t = _tiles([[0, 1]], [[0, 1]], [[[0], [-1]]], [1])
    f = pc.check_tiles(t, _COIR2, _MASK2, 2, 2, None, "t")
    assert "REPRO-P004" in _rules(f)
    assert any("dropped" in x.message for x in f)


def test_p004_dma_chain_wrong_source():
    t = _tiles([[0, 1]], [[1, 0]], [[[0], [1]]], [2])
    f = pc.check_tiles(t, _COIR2, _MASK2, 2, 2, None, "t")
    assert any("wrong" in x.message and x.rule == "REPRO-P004" for x in f)


def _sharded(idx, send):
    return NS(indices=np.asarray(idx, np.int32),
              send_rows=np.asarray(send, np.int32))


def test_p005_sharded_clean_and_violations():
    s, vs, h = 2, 4, 2
    send = np.full((s, s, h), -1, np.int32)
    send[1, 0, 1] = 2  # shard 1 sends its row 2 into shard 0's slot 1
    own = np.zeros((s, vs, 1), np.int32)
    # clean: shard 0 reads halo slot d=1,j=1 -> coded vs + 1*h + 1 = 7
    idx = own.copy()
    idx[0, 0, 0] = vs + 1 * h + 1
    assert pc.check_sharded_conv(_sharded(idx, send), vs, vs, s, "p") == []
    # self-halo: shard 0 referencing a slot it would send itself
    idx_self = own.copy()
    idx_self[0, 0, 0] = vs + 0 * h + 0
    f = pc.check_sharded_conv(_sharded(idx_self, send), vs, vs, s, "p")
    assert any("itself" in x.message and x.rule == "REPRO-P005" for x in f)
    # unsent slot: nobody populates shard 1's slot j=0 for shard 0
    idx_unsent = own.copy()
    idx_unsent[0, 0, 0] = vs + 1 * h + 0
    f = pc.check_sharded_conv(_sharded(idx_unsent, send), vs, vs, s, "p")
    assert any("never send" in x.message for x in f)
    # send rows must be local to the sender
    bad_send = send.copy()
    bad_send[1, 0, 1] = vs + 3
    f = pc.check_sharded_conv(_sharded(idx, bad_send), vs, vs, s, "p")
    assert any("send rows" in x.message.lower() for x in f)


# ---------------------------------------------------------------------------
# real built plan (integration) + cache keys (REPRO-P006)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    from repro import engine
    from repro.data.scenes import N_CLASSES, make_scene
    from repro.models.scn import UNetConfig
    from repro.sparse.tensor import SparseVoxelTensor
    res, cap = 16, 512
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=res, capacity=cap,
                     n_classes=N_CLASSES)
    coords, feats, _, mask = make_scene(0, resolution=res, capacity=cap)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    spec = engine.build_plan_spec([t], cfg, mem_budget=64 * 1024)
    plan = engine.build_scene_plan_host(t, cfg, spec=spec, plan_tiles=True)
    return t, cfg, plan


def test_real_plan_is_clean(built):
    _, _, plan = built
    assert pc.check_scene_plan(plan) == []


def test_real_plan_corrupted_tables_are_caught(built):
    _, _, plan = built
    lvl = next(l for l in plan.levels if l.sub.tiles is not None)
    v = int(np.asarray(lvl.mask).shape[0])
    orow = np.array(lvl.sub.tiles.out_rows, np.int32, copy=True)
    orow[0, 0] = v + 7  # beyond the trash row
    bad = NS(out_rows=orow,
             in_rows=np.asarray(lvl.sub.tiles.in_rows),
             local_idx=np.asarray(lvl.sub.tiles.local_idx),
             pair_counts=np.asarray(lvl.sub.tiles.pair_counts))
    f = pc.check_tiles(bad, lvl.sub.coir, np.asarray(lvl.mask), v, v,
                       None, "t")
    assert "REPRO-P003" in _rules(f)


def test_p006_cache_keys_rotate(built):
    from repro.engine.backends import BreakerBoard, default_registry
    from repro.engine.plan import PlanCache
    t, cfg, _ = built
    cache = PlanCache(capacity=t.capacity)
    assert pc.check_cache_keys(
        cache, t, cfg, breakers=BreakerBoard(default_registry())) == []

    class Frozen:  # no generation counter at all
        def __repr__(self):
            return "Frozen()"

    f = pc.check_cache_keys(cache, t, cfg, breakers=Frozen())
    assert any("no generation" in x.message for x in f)

    class Hidden:  # has a counter but a repr that does not mix it
        generation = 0

        def __repr__(self):
            return "Hidden()"

    f = pc.check_cache_keys(cache, t, cfg, breakers=Hidden())
    assert any(x.rule == "REPRO-P006" and "rotate" in x.message for x in f)


def test_plan_cache_under_runtime_lock_check(monkeypatch, built):
    monkeypatch.setenv("REPRO_LOCK_CHECK", "1")
    from repro.engine.plan import PlanCache
    t, cfg, plan = built
    cache = PlanCache(capacity=t.capacity)
    assert isinstance(cache._lock, rt._CheckedLock)
    key = cache.key_for(t, cfg)
    assert cache.adopt(key, plan, device=False) is plan
    assert cache.adopt(key, plan, device=False) is plan  # hit path
    assert cache.invalidate() == 1


# ---------------------------------------------------------------------------
# hlo gates (REPRO-H001..H003)
# ---------------------------------------------------------------------------

def test_h001_flags_gather_and_scatter():
    def g(x, i):
        return jnp.take(x, i, axis=0)

    text = hg.compiled_text(g, jnp.ones((16, 4)), jnp.array([1, 3]))
    f = hg.forbidden_ops(text, where="g")
    assert any(x.rule == "REPRO-H001" and "gather" in x.message for x in f)

    # CPU XLA rewrites scatter into loops before final HLO, so seed the
    # scatter side with literal HLO text (forbidden_ops accepts text)
    text = textwrap.dedent("""\
        ENTRY %main (p0: f32[8], p1: s32[2], p2: f32[2]) -> f32[8] {
          %p0 = f32[8] parameter(0)
          %p1 = s32[2] parameter(1)
          %p2 = f32[2] parameter(2)
          ROOT %sc = f32[8] scatter(%p0, %p1, %p2), to_apply=%add
        }
    """)
    f = hg.forbidden_ops(text, where="s")
    assert any(x.rule == "REPRO-H001" and "scatter" in x.message for x in f)


def test_h001_clean_matmul():
    text = hg.compiled_text(lambda a, b: a @ b,
                            jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert hg.forbidden_ops(text) == []


def test_h002_compile_budget():
    jf = jax.jit(lambda x: x * 2)
    jf(jnp.ones((4,)))
    jf(jnp.ones((8,)))
    assert hg.compile_count(jf) == 2
    assert _rules(hg.gate_compile_budget(jf, 1)) == ["REPRO-H002"]
    assert hg.gate_compile_budget(jf, 2) == []
    assert _rules(hg.gate_compile_budget(3, 2, where="engine")) == \
        ["REPRO-H002"]
    with pytest.raises(TypeError):
        hg.compile_count(lambda x: x)


def test_h003_vmem_budget():
    assert hg.gate_vmem_budget(NS(delta_o=16, delta_i=48, block_n=8), 8) \
        == []
    f = hg.gate_vmem_budget(
        NS(delta_o=4096, delta_i=65536, block_n=512), 256)
    assert _rules(f) == ["REPRO-H003"]
    # non-tile dispatch passes trivially
    assert hg.gate_vmem_budget(NS(delta_o=0, delta_i=0, block_n=None), 8) \
        == []
    got = hg.modeled_vmem_bytes(delta_o=2, delta_i=3, c_in=4, block_n=5,
                                k=6, itemsize=4)
    want = (2 * 3 * 4 + 2 * 5) * 4 + 2 * 2 * 6 * 4 + 2 * 6 * 4 * 5 * 4
    assert got == want


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_static_passes_clean(tmp_path, capsys):
    from repro.analysis.__main__ import main
    out = tmp_path / "findings.json"
    assert main(["--only", "lint", "--only", "locks",
                 "--json", str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["n_findings"] == 0
    assert set(data["lock_graph"]["locks"]) == set(rt.LOCK_ORDER)


def test_cli_counts_findings(tmp_path):
    from repro.analysis.__main__ import main
    bad = tmp_path / "src" / "repro"
    bad.mkdir(parents=True)
    (bad / "mod.py").write_text(
        "import threading\nL = threading.Lock()\n"
        "t = threading.Thread(target=print)\n")
    rc = main(["--root", str(tmp_path), "--only", "lint", "--only", "locks"])
    assert rc == 3  # L003 name + L003 daemon + C003 raw lock
