"""SOAR / SPADE / CAROM / scheduler behaviour."""
import numpy as np

from repro.core import carom, schedule, soar, spade


def test_soar_is_permutation(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 200)
    n = int(t.n_active())
    assert len(res.order) == n
    assert len(np.unique(res.order)) == n
    sizes = np.diff(res.chunk_starts)
    assert sizes.max() <= 200 and sizes.min() > 0


def test_soar_beats_raster(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 128)
    rast = soar.raster_order(np.asarray(t.coords), np.asarray(t.mask))
    a_soar = soar.tiled_unique_input_accesses(res.order, idx, 128)
    a_rast = soar.tiled_unique_input_accesses(rast, idx, 128)
    assert a_soar < a_rast  # Fig 23: SOAR saves input fetches


def test_soar_hierarchical(shell):
    t, nbr, idx = shell
    res = soar.soar_hierarchical(nbr, np.asarray(t.mask), [64, 512])
    n = int(t.n_active())
    assert len(np.unique(res.order)) == n


def test_sparsity_attributes_shape_and_trends(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 256)
    attrs = spade.extract_attributes(idx, np.asarray(t.mask), res.order)
    # SA_I falls with region size (surface/volume); ARF ~ constant (Fig 15)
    assert attrs.sa_minor_avg[0] >= attrs.sa_minor_avg[-1]
    assert np.ptp(attrs.arf_avg) < 0.5
    assert np.all(attrs.sa_minor_alloc_sst >= attrs.sa_minor_avg - 1e-9)
    assert np.all(attrs.sa_minor_alloc_rst <= attrs.sa_minor_alloc_sst + 1e-9)
    alpha, corr = spade.fit_surface_ratio(attrs)
    assert alpha > 0 and corr > 0.5


def test_spade_explore_respects_budget(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 256)
    attrs = spade.extract_attributes(idx, np.asarray(t.mask), res.order)
    v = int(t.n_active())
    layer = spade.LayerSpec("L", v, v, 27, 64, 96, 2)
    for budget in (32 * 1024, 64 * 1024, 256 * 1024):
        df = spade.explore(layer, {"CIRF": attrs, "CORF": attrs}, budget)
        assert df.tile_elems * layer.dtype_bytes <= budget * 1.001
    # larger memory -> no worse dataflow
    small = spade.explore(layer, {"CIRF": attrs}, 32 * 1024)
    big = spade.explore(layer, {"CIRF": attrs}, 1024 * 1024)
    assert big.da_elems <= small.da_elems * 1.001


def test_spade_walk_pattern_semantics(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 256)
    attrs = spade.extract_attributes(idx, np.asarray(t.mask), res.order)
    layer = spade.LayerSpec("L", 4096, 4096, 27, 64, 64, 2)
    # WS: weights fetched once; IS: inputs once; OS: outputs once (Eqn 5)
    for wp, idx_term in (("WS", 0), ("IS", 1), ("OS", 2)):
        da, br = spade.data_accesses(layer, attrs, 256, 32, 32, wp, "CIRF")
        base = {0: 64 * 64 * 27,
                1: attrs.at(256, "sa_minor_avg") * 4096 * 64,
                2: 4096 * 64 + attrs.at(256, "arf_avg") * 4096}[idx_term]
        assert abs(br[idx_term] - base) / base < 1e-6


def test_offline_table_near_optimal(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 256)
    attrs = spade.extract_attributes(idx, np.asarray(t.mask), res.order)
    v = int(t.n_active())
    layer = spade.LayerSpec("L", v, v, 27, 32, 32, 2)
    msa = spade.meta_attributes([attrs])
    table = spade.build_offline_table([layer], msa, 64 * 1024)
    arf = float(attrs.arf_avg[0])
    plan = spade.otf_lookup(table, layer, arf)
    direct = spade.explore(layer, {"CIRF": attrs, "CORF": attrs}, 64 * 1024)
    # offline plan within 2x of the input-specific optimum (paper: marginal loss)
    assert plan.da_elems <= 2.0 * direct.da_elems


def test_carom_constraint_and_value(shell):
    t, nbr, idx = shell
    res = soar.soar_order(nbr, np.asarray(t.mask), 256)
    attrs = spade.extract_attributes(idx, np.asarray(t.mask), res.order)
    v = int(t.n_active())
    layer = spade.LayerSpec("L", v, v, 27, 64, 64, 2)
    levels = [carom.MemLevel("L2", 2 << 20, 16, 1024),
              carom.MemLevel("L1", 64 << 10, 64, 1024)]
    plans = carom.carom_search(layer, {"CIRF": attrs, "CORF": attrs}, levels)
    assert len(plans) == 2
    greedy = carom.greedy_search(layer, {"CIRF": attrs, "CORF": attrs}, levels)
    # CAROM may pay more at the outer level, never more at both
    assert plans[0].da_elems >= greedy[0].da_elems * 0.999


def test_schedulers():
    rng = np.random.default_rng(3)
    work = rng.pareto(1.5, 100) * 100 + 10
    naive = schedule.schedule_naive(work, 8)
    paper = schedule.schedule_round_robin_sorted(work, 8)
    lpt = schedule.schedule_lpt(work, 8)
    ideal = work.sum() / 8
    assert lpt.makespan <= paper.makespan <= naive.makespan + 1e-9
    assert lpt.makespan >= ideal - 1e-9
    for a in (naive, paper, lpt):
        assert np.isclose(a.per_core_work.sum(), work.sum())
    # overlap model: sorted schedule no slower than naive under the bus model
    xfer = work * 0.1
    t_paper = schedule.phase_overlap_makespan(paper, work, xfer, 1.0, 10.0)
    t_naive = schedule.phase_overlap_makespan(naive, work, xfer, 1.0, 10.0)
    assert t_paper <= t_naive * 1.05


def test_soar_sa_alloc_no_worse_than_random(shell):
    """Integration: SOAR ordering gives SPADE an SA_I allocation no worse
    than a random permutation at every region size (locality -> smaller
    unique-input working sets, Fig 15/23)."""
    t, nbr, idx = shell
    mask = np.asarray(t.mask)
    res = soar.soar_order(nbr, mask, 256)
    rand = np.random.default_rng(11).permutation(np.flatnonzero(mask))
    a_soar = spade.extract_attributes(idx, mask, res.order)
    a_rand = spade.extract_attributes(idx, mask, rand)
    assert np.all(a_soar.sa_minor_alloc_sst <= a_rand.sa_minor_alloc_sst + 1e-9)
    assert np.all(a_soar.sa_minor_avg <= a_rand.sa_minor_avg + 1e-9)
