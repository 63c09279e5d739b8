"""Coloring / critical-path / LPT placement tests (paper §5.2 machinery)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import coloring
from repro.distributed import partition


def _valid_coloring(shape, colors):
    colors = np.asarray(colors).reshape(-1)
    for v, nbrs in coloring._neighbors(shape):
        for u in nbrs:
            if colors[u] == colors[v]:
                return False
    return True


class TestColoring:
    def test_naive_is_valid_8_colors(self):
        shape = (4, 4, 4)
        c = coloring.naive_coloring(shape)
        assert c.max() <= 7
        assert _valid_coloring(shape, c)

    @settings(max_examples=10, deadline=None)
    @given(
        nx=st.integers(1, 5), ny=st.integers(1, 5), nz=st.integers(1, 4),
        seed=st.integers(0, 99),
    )
    def test_load_aware_is_valid(self, nx, ny, nz, seed):
        shape = (nx, ny, nz)
        rng = np.random.default_rng(seed)
        loads = rng.pareto(1.5, nx * ny * nz) * 100
        c = coloring.load_aware_coloring(shape, loads)
        assert _valid_coloring(shape, c)

    def test_load_aware_shortens_critical_path_on_skewed_loads(self):
        """The paper's Fig.12 claim: SCHED coloring <= naive coloring T_inf."""
        shape = (6, 6, 6)
        rng = np.random.default_rng(0)
        loads = rng.pareto(1.0, 6 * 6 * 6) * 100 + 1
        naive = coloring.naive_coloring(shape)
        smart = coloring.load_aware_coloring(shape, loads)
        t_naive = coloring.critical_path(shape, naive, loads)
        t_smart = coloring.critical_path(shape, smart, loads)
        assert t_smart <= t_naive * 1.001

    def test_critical_path_bounds(self):
        shape = (3, 3, 3)
        loads = np.ones(27)
        c = coloring.naive_coloring(shape)
        tinf = coloring.critical_path(shape, c, loads)
        assert loads.max() <= tinf <= loads.sum()

    def test_simulated_schedule_respects_graham(self):
        shape = (5, 5, 3)
        rng = np.random.default_rng(1)
        loads = rng.pareto(1.2, 75) * 50 + 1
        c = coloring.load_aware_coloring(shape, loads)
        T1 = loads.sum()
        Tinf = coloring.critical_path(shape, c, loads)
        for P in (2, 4, 8, 16):
            tp = coloring.simulate_schedule(shape, c, loads, P)
            assert tp <= coloring.graham_bound(T1, Tinf, P) + 1e-6
            assert tp >= max(T1 / P, Tinf) - 1e-6

    def test_replicate_critical_reduces_tinf(self):
        shape = (4, 4, 2)
        loads = np.ones(32)
        loads[0] = 500.0  # one dominating subdomain
        c = coloring.load_aware_coloring(shape, loads)
        t0 = coloring.critical_path(shape, c, loads)
        eff, rep = coloring.replicate_critical(shape, c, loads, P=8)
        t1 = coloring.critical_path(shape, c, eff)
        assert t1 < t0
        assert rep[0] > 1  # the heavy subdomain got replicated


class TestLPT:
    def test_lpt_beats_block_on_skew(self):
        rng = np.random.default_rng(2)
        loads = np.sort(rng.pareto(1.0, 256) * 100)[::-1].copy()
        stats = partition.imbalance_stats(loads, 16)
        assert stats["lpt_makespan"] <= stats["block_makespan"]
        # LPT bound: makespan <= ideal + largest tile (a single dominating
        # tile can't be fixed by placement — that's what PD-REP is for)
        assert stats["lpt_makespan"] <= stats["ideal"] + loads.max() + 1e-9

    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(1, 200), P=st.integers(1, 32), seed=st.integers(0, 99)
    )
    def test_lpt_is_complete_and_bounded(self, n, P, seed):
        rng = np.random.default_rng(seed)
        loads = rng.random(n) * 10
        a = partition.lpt_assign(loads, P)
        # every tile assigned exactly once
        assert sorted(t for ts in a.tiles_of_device for t in ts) == list(
            range(n)
        )
        # Graham's 4/3 bound for LPT
        opt_lb = max(loads.max(initial=0.0), loads.sum() / P)
        assert a.makespan <= 4 / 3 * opt_lb + 1e-9

    def test_round_robin_split_conserves_counts(self):
        counts = np.array([[5, 0], [17, 3]])
        out = partition.split_counts_round_robin(counts, 4)
        assert out.shape == (4, 2, 2)
        np.testing.assert_array_equal(out.sum(axis=0), counts)
        assert out.max() - out.min(axis=0).min() <= 5  # near-even


class TestPlanner:
    def test_planner_prefers_pd_for_sparse_large_grid(self):
        """Flu-like: huge grid, few points -> init-bound -> not DR."""
        from repro.core import plan
        from repro.core.geometry import Domain

        dom = Domain(gx=581, gy=1536, gt=5951, sres=1, tres=1, hs=5, ht=7)
        pick, table = plan.choose(dom, 31_478, (16, 16))
        assert pick != "dr"
        assert table["dr"]["init_s"] > table["pd"]["init_s"]

    def test_planner_tables_have_all_strategies(self):
        from repro.core import plan
        from repro.core.geometry import Domain

        dom = Domain(gx=131, gy=61, gt=84, sres=1, tres=1, hs=2, ht=3)
        _, table = plan.choose(dom, 588_189, (2, 16, 16))
        assert set(table) == {"dr", "dd", "pd", "pd_xt", "pd_xyt",
                              "dd_lpt", "hybrid"}
        for v in table.values():
            assert v["total_s"] > 0

    @pytest.mark.parametrize("platform,kind,want", [
        ("cpu", "cpu", "V5E"),
        ("tpu", "TPU v5 lite", "V5E"),
        ("tpu", "TPU v99 imaginary", None),
    ])
    def test_default_hw_by_device_kind(self, monkeypatch, platform, kind,
                                       want):
        """Peaks come from one table keyed by device_kind; a kind with no
        entry raises instead of being priced as some other chip."""
        import types

        import jax
        from repro.core import plan

        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])
        if want is None:
            with pytest.raises(ValueError, match="device_kind"):
                plan.default_hw()
        else:
            assert plan.default_hw() is getattr(plan, want)


# The planner's picks on a 2x2 ``data`` x ``model`` mesh for every Table 2
# instance up to PollenUS's 588,189 events (eBird's 292 M are left out),
# priced with the CPU's default hardware model, which is the v5e's.
# "chip" marks a pick that a run on four v5e chips confirms: Flu_Mr-Hb is
# the ``flu_mr_hb_x4.build`` cell, and PERF.md records ``dd`` for
# PollenUS_Hr-Lb on 2x2.
MESH_PLANS_2X2 = {
    "Dengue_Lr-Lb": "dd",
    "Dengue_Lr-Hb": "dd",
    "Dengue_Hr-Lb": "dd",
    "Dengue_Hr-Hb": "dd",
    "Dengue_Hr-VHb": "dd",
    "PollenUS_Lr-Lb": "pd",
    "PollenUS_Hr-Lb": "dd",       # chip
    "PollenUS_Hr-Mb": "dd_lpt",
    "PollenUS_Hr-Hb": "dd_lpt",
    "PollenUS_VHr-Lb": "dd",
    "PollenUS_VHr-VLb": "dd",
    "Flu_Lr-Lb": "dd",
    "Flu_Lr-Hb": "dd",
    "Flu_Mr-Lb": "dd",
    "Flu_Mr-Hb": "dd",            # chip
    "Flu_Hr-Lb": "dd",
    "Flu_Hr-Hb": "dd",
}


@pytest.mark.parametrize("name", sorted(MESH_PLANS_2X2))
def test_mesh_plan_on_2x2(name):
    """A CPU run plans a mesh exactly as the v5e does."""
    import types

    from repro.core import get_instance
    from repro.core.api import _auto_strategy

    inst = get_instance(name)
    mesh = types.SimpleNamespace(shape={"data": 2, "model": 2})
    got = _auto_strategy(inst.domain(), inst.n, mesh, ("data", "model"),
                         None, inst.points())
    assert got == MESH_PLANS_2X2[name]
