"""System tests for the JBOF simulator: paper-claim reproduction bands +
conservation/sanity properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from repro.core import harvest as hv
from repro.jbof import bom, platforms, sim, ssd, workloads as wl
from repro.obs import metrics as obs_m


def _run(plat_name, wls, n=300, seed=0, **kw):
    arr = wl.arrivals(wls, n, seed=seed)
    plat = platforms.ALL[plat_name]()
    if kw:
        plat = plat._replace(**kw)
    return sim.simulate(plat, wls, arr)


MICRO_READ = [wl.micro(True, 64.0)] * 6 + [wl.idle()] * 6
MICRO_WRITE = [wl.micro(False, 64.0)] * 6 + [wl.idle()] * 6
RAND_READ = [wl.micro(True, 4.0, qd=1, random_access=True)] * 6 + [wl.idle()] * 6


class TestPaperClaims:
    """Quantitative bands around the paper's headline numbers."""

    def test_fig4b_calibration_read(self):
        r = _run("Shrunk", MICRO_READ)
        assert 0.90 < float(r.proc_util[:6].mean()) <= 1.0 + 1e-4  # paper 0.954
        assert 0.35 < float(r.flash_util[:6].mean()) < 0.50        # paper 0.422

    def test_shrunk_loses_reads_not_writes(self):
        conv_r = _run("Conv", MICRO_READ)
        shr_r = _run("Shrunk", MICRO_READ)
        loss = float(shr_r.throughput_bps[:6].mean()
                     / conv_r.throughput_bps[:6].mean()) - 1
        assert -0.60 < loss < -0.35  # 64K reads are proc-bound

        conv_w = _run("Conv", MICRO_WRITE)
        shr_w = _run("Shrunk", MICRO_WRITE)
        loss_w = float(shr_w.throughput_bps[:6].mean()
                       / conv_w.throughput_bps[:6].mean()) - 1
        assert abs(loss_w) < 0.05    # writes are flash-bound

    def test_xbof_matches_conv_with_half_resources(self):
        conv = _run("Conv", MICRO_READ)
        xbof = _run("XBOF", MICRO_READ)
        rel = float(xbof.throughput_bps[:6].mean()
                    / conv.throughput_bps[:6].mean())
        assert rel > 0.90, rel  # paper: "comparable"

    def test_utilization_gain_over_shrunk(self):
        shr = _run("Shrunk", MICRO_READ)
        xb = _run("XBOF", MICRO_READ)
        u_s = float((shr.proc_util[:6].mean() + shr.proc_util[6:].mean()) / 2)
        u_x = float((xb.proc_util[:6].mean() + xb.proc_util[6:].mean()) / 2)
        assert u_x - u_s > 0.35  # paper +0.504

    def test_vh_helps_writes_only_and_pays_copyback(self):
        shr_r = _run("Shrunk", MICRO_READ)
        vh_r = _run("VH", MICRO_READ)
        assert abs(float(vh_r.throughput_bps[:6].mean()
                         / shr_r.throughput_bps[:6].mean()) - 1) < 0.02

        shr_w = _run("Shrunk", MICRO_WRITE)
        vh_w = _run("VH", MICRO_WRITE)
        vhi_w = _run("VH(ideal)", MICRO_WRITE)
        assert float(vhi_w.throughput_bps[:6].mean()) > \
            float(vh_w.throughput_bps[:6].mean())
        # copyback inflates drive writes (paper: +0.29 DWPD on traces)
        assert float(vh_w.dwpd[:6].mean()) > float(shr_w.dwpd[:6].mean())

    def test_dram_harvesting_fixes_miss_ratio(self):
        shr = _run("Shrunk", RAND_READ)
        xb = _run("XBOF", RAND_READ)
        assert 0.45 < float(shr.miss_ratio[:6].mean()) < 0.55  # paper 0.497
        assert float(xb.miss_ratio[:6].mean()) <= 0.105        # target <10%
        assert float(xb.latency_s[:6].mean()) < float(shr.latency_s[:6].mean())

    def test_oc_host_bottleneck(self):
        """OC loses heavily on proc-bound reads, nothing on flash-bound
        writes; the paper's -27.8% is the read/write-size AVERAGE (fig09)."""
        conv = _run("Conv", MICRO_READ)
        oc = _run("OC", MICRO_READ)
        loss_r = float(oc.throughput_bps[:6].mean()
                       / conv.throughput_bps[:6].mean()) - 1
        assert -0.65 < loss_r < -0.25

        conv_w = _run("Conv", MICRO_WRITE)
        oc_w = _run("OC", MICRO_WRITE)
        loss_w = float(oc_w.throughput_bps[:6].mean()
                       / conv_w.throughput_bps[:6].mean()) - 1
        assert abs(loss_w) < 0.05
        # the figure-level average (reads+writes) lands near the paper's
        # -0.278 — asserted loosely here, precisely in benchmarks/fig09
        assert -0.45 < (loss_r + loss_w) / 2 < -0.12

    def test_bom_savings(self):
        conv = bom.platform_cost("Conv")["total"]
        xbof = bom.platform_cost("XBOF")["total"]
        assert 0.12 < 1 - xbof / conv < 0.26  # paper 0.190

    def test_lender_impact_small(self):
        wls = [wl.micro(True, 64.0)] * 6 + [wl.moderate(False, 4.0, 8)] * 6
        shr = _run("Shrunk", wls)
        xb = _run("XBOF", wls)
        impact = float(xb.throughput_bps[6:].mean()
                       / shr.throughput_bps[6:].mean()) - 1
        assert impact > -0.10  # paper -0.013


class TestDramDescriptorHarvest:
    """§4.5 via the management plane: borrowed segments derive exclusively
    from DRAM descriptor claims (assist_matrix), with the §4.6 remote-access
    cost model on borrowed-segment hits."""

    def test_grants_flow_through_claims_and_conserve(self):
        r = _run("XBOF", RAND_READ)
        b = np.asarray(r.borrowed_seg)
        assert (b[:6] > 100).all()       # busy nodes borrowed via claims
        assert (b[6:] < 1e-5).all()      # idle lenders did not
        own = platforms.ALL["XBOF"]().ssd_config.dram_segments
        # six idle lenders can publish at most (own - lend floor) each
        assert b.sum() <= (own - hv.DRAM_MIN_KEEP_SEGMENTS) * 6 + 1e-2
        # and the borrowed cache still lands the §4.5 miss target
        assert float(r.miss_ratio[:6].mean()) <= 0.105

    def test_remote_hits_pay_cxl_hop(self):
        """Mapping-cache hits served from borrowed segments are not free:
        inflating the CXL hop cost must show up in read latency (the old
        model taxed only WAL writes, so this knob did nothing on reads)."""
        base = _run("XBOF", RAND_READ)
        taxed = _run("XBOF", RAND_READ, cxl_hop_s=ssd.T_CXL_HOP * 400)
        assert float(np.asarray(taxed.borrowed_seg)[:6].mean()) > 0
        assert float(taxed.latency_s[:6].mean()) > \
            float(base.latency_s[:6].mean()) * 1.05

    def test_shrunk_never_borrows(self):
        r = _run("Shrunk", RAND_READ)
        assert float(np.abs(np.asarray(r.borrowed_seg)).max()) == 0.0


class TestBackboneLinkHarvest:
    """XBOF+ (§3 full disaggregation): FLASH_BW and LINK_BW flow through
    the same `ResourceManager.round()` as processor clocks."""

    BACKBONE_BOUND = [wl.micro(False, 4.0)] * 3 + [wl.idle()] * 3
    MIXED = [wl.micro(False, 64.0)._replace(name="mixed64K", read_ratio=0.5)] * 3 \
        + [wl.idle()] * 3

    def test_idle_backbones_assist_busy_ssds(self):
        """Backbone-bound (4 KB random-ish writes, SLC-amplified): XBOF's
        proc+DRAM harvesting cannot help (proc has headroom), but FLASH_BW
        harvesting redistributes idle SSDs' channel time."""
        shr = _run("Shrunk", self.BACKBONE_BOUND, n=200)
        xb = _run("XBOF", self.BACKBONE_BOUND, n=200)
        xbp = _run("XBOF+", self.BACKBONE_BOUND, n=200)
        t_shr = float(shr.throughput_bps[:3].mean())
        t_xb = float(xb.throughput_bps[:3].mean())
        t_xbp = float(xbp.throughput_bps[:3].mean())
        assert abs(t_xb / t_shr - 1) < 0.05     # proc/DRAM harvest: no gain
        assert t_xbp / t_shr > 1.4              # backbone harvest: big gain
        # the gain is the lenders' channel time: idle SSDs' backbones busy
        assert float(xbp.flash_util[3:].mean()) > \
            float(shr.flash_util[3:].mean()) + 0.3

    def test_link_harvest_relieves_fabric_bound_assist(self):
        """Mixed read+write streams: once proc AND backbone assists flow,
        the borrower's CXL port saturates; LINK_BW harvesting pools idle
        ports and lifts throughput further."""
        base = platforms.ALL["XBOF+"]()
        arr = wl.arrivals(self.MIXED, 300, seed=0)
        no_link = sim.simulate(base._replace(harvest_link=False), self.MIXED, arr)
        full = sim.simulate(base, self.MIXED, arr)
        t_no = float(no_link.throughput_bps[:3].mean())
        t_full = float(full.throughput_bps[:3].mean())
        assert t_full / t_no > 1.05
        # pooled bytes really crossed the fabric
        assert float(full.cxl_bytes[:3].sum()) > 0

    def test_flash_transfer_never_exceeds_lender_capacity(self):
        """Conservation at the system level: donated channel time shows up
        as lender busy time, and no utilization exceeds 1."""
        xbp = _run("XBOF+", self.BACKBONE_BOUND, n=200)
        v = np.asarray(xbp.flash_util)
        assert (v >= -1e-6).all() and (v <= 1.01).all()

    def test_xbof_plus_no_worse_on_proc_bound_reads(self):
        """The new rtypes must not regress the paper's headline scenario
        (proc-bound reads are PROCESSOR-harvest territory)."""
        xb = _run("XBOF", MICRO_READ, n=200)
        xbp = _run("XBOF+", MICRO_READ, n=200)
        rel = float(xbp.throughput_bps[:6].mean()
                    / xb.throughput_bps[:6].mean())
        assert rel > 0.95


class TestSimInvariants:
    def test_served_never_exceeds_flash_roofline(self):
        r = _run("Conv", MICRO_READ)
        assert float(r.throughput_bps.max()) <= ssd.PEAK_READ_BPS * 1.01

    def test_utilizations_bounded(self):
        for name in ["Conv", "XBOF", "VH"]:
            r = _run(name, MICRO_READ)
            for field in ["proc_util", "flash_util"]:
                v = np.asarray(getattr(r, field))
                assert (v >= -1e-6).all() and (v <= 1.01).all(), (name, field)

    def test_energy_positive_monotone_with_work(self):
        r_busy = _run("Conv", MICRO_READ)
        r_idle = _run("Conv", [wl.idle()] * 12)
        assert float(r_busy.energy_j) > float(r_idle.energy_j) > 0

    def test_idle_system_serves_nothing_much(self):
        r = _run("XBOF", [wl.idle()] * 12)
        assert float(r.throughput_bps.mean()) < 0.05 * ssd.PEAK_READ_BPS

    def test_more_lenders_never_hurt(self):
        w = wl.TABLE2["Ali-0"]
        thr = []
        for nb, nl in [(6, 2), (6, 6)]:
            wls = [w] * nb + [wl.idle()] * nl
            r = _run("XBOF", wls)
            thr.append(float(r.throughput_bps[:nb].mean()))
        assert thr[1] >= thr[0] * 0.98

    def test_latency_exceeds_service_floor(self):
        r = _run("Conv", RAND_READ)
        assert float(r.latency_s[:6].min()) > ssd.T_READ_AVG  # >= flash read


BUSY = wl.micro(False, 4.0, qd=4, random_access=True)


class TestMultiEnclosure:
    """`simulate(..., cfg=SimConfig(n_enclosures=E))`: the topology
    plane's multi-JBOF
    scale-out (DESIGN.md §11). Enclosure 0 runs proc/DRAM-starved random
    writers, enclosure 1 sits idle — intra-enclosure harvesting cannot
    help, so any relief must cross the fabric."""

    def _split(self, **kw):
        wls = [BUSY] * 6 + [wl.idle()] * 6
        arr = wl.arrivals(wls, 200, seed=3)
        plat = platforms.xbof()._replace(**{k: v for k, v in kw.items()
                                            if k != "fabric_federation"})
        return sim.simulate(plat, wls, arr, cfg=sim.SimConfig(
            n_enclosures=2,
            fabric_federation=kw.get("fabric_federation", True)))

    def test_enclosure_count_must_divide_fleet(self):
        wls = [BUSY] * 6 + [wl.idle()] * 6
        arr = wl.arrivals(wls, 50, seed=0)
        try:
            sim.simulate(platforms.xbof(), wls, arr,
                         cfg=sim.SimConfig(n_enclosures=5))
        except ValueError as e:
            assert "enclosure" in str(e)
        else:
            raise AssertionError("n=12, E=5 should be rejected")

    def test_single_enclosure_is_the_flat_sim_bitwise(self):
        """E=1 must take the pre-topology code path exactly: no fabric
        terms in the program, identical outputs."""
        wls = [BUSY] * 6 + [wl.idle()] * 6
        arr = wl.arrivals(wls, 100, seed=1)
        a = sim.simulate(platforms.xbof(), wls, arr)
        b = sim.simulate(platforms.xbof(), wls, arr,
                         cfg=sim.SimConfig(n_enclosures=1))
        np.testing.assert_array_equal(np.asarray(a.latency_s),
                                      np.asarray(b.latency_s))
        np.testing.assert_array_equal(np.asarray(a.miss_ratio),
                                      np.asarray(b.miss_ratio))

    def test_federation_moves_far_segments_to_the_busy_half(self):
        r = self._split()
        far = np.asarray(r.borrowed_far)
        assert far[:6].sum() > 1.0        # busy half borrowed across fabric
        assert far[6:].sum() < 1e-6       # idle half borrowed nothing

    def test_federation_off_keeps_enclosures_isolated(self):
        r = self._split(fabric_federation=False)
        assert float(np.asarray(r.borrowed_far).sum()) == 0.0

    def test_federation_relieves_busy_latency_at_cheap_fabric(self):
        on = self._split(fabric_extra_hops=1.0)
        off = self._split(fabric_federation=False)
        lat_on = float(np.asarray(on.latency_s[:6]).mean())
        lat_off = float(np.asarray(off.latency_s[:6]).mean())
        assert lat_on < lat_off
        miss_on = float(np.asarray(on.miss_ratio[:6]).mean())
        miss_off = float(np.asarray(off.miss_ratio[:6]).mean())
        assert miss_on < miss_off

    def test_pricier_fabric_never_helps_more(self):
        cheap = self._split(fabric_extra_hops=1.0)
        dear = self._split(fabric_extra_hops=256.0)
        assert (float(np.asarray(cheap.latency_s[:6]).mean())
                <= float(np.asarray(dear.latency_s[:6]).mean()) + 1e-9)


def _fleet(e, obs=False, per=4, windows=12):
    """`e` enclosures of `per` SSDs, half busy and half idle in each."""
    wls = ([BUSY] * (per // 2) + [wl.idle()] * (per - per // 2)) * e
    arr = wl.arrivals(wls, windows, seed=3)
    return wls, arr, sim.SimConfig(n_enclosures=e, warmup=2,
                                   obs=obs_m.ObsConfig(enabled=obs))


def _eager_binds(monkeypatch, fn):
    """Primitives bound outside any trace while `fn` runs: each is one
    eager dispatch from the host."""
    count, bind = [0], jax_core.Primitive.bind

    def counting(self, *args, **params):
        if isinstance(jax_core.trace_ctx.trace, jax_core.EvalTrace):
            count[0] += 1
        return bind(self, *args, **params)

    with monkeypatch.context() as m:
        m.setattr(jax_core.Primitive, "bind", counting)
        fn()
    return count[0]


@pytest.mark.parametrize("obs", [False, True], ids=["plain", "obs"])
def test_simulate_dispatches_do_not_grow_with_enclosures(monkeypatch, obs):
    """The host's eager dispatches over one `simulate` call are the same at
    4 and at 32 enclosures of 4 SSDs: nothing is built per enclosure."""
    counts = []
    for e in (4, 32):
        wls, arr, cfg = _fleet(e, obs)
        counts.append(_eager_binds(
            monkeypatch,
            lambda: sim.simulate(platforms.xbof(), wls, arr, cfg)))
    assert counts[0] == counts[1], counts


def _stacked_state(plat, e, nl, tcfg, obs):
    """The initial carry as `e` stacked copies of one enclosure's."""
    return jax.tree.map(lambda a: jnp.stack([a] * e),
                        sim._init_state(plat, nl, tcfg, obs))


def _assert_trees_equal(got, want):
    g, gdef = jax.tree.flatten(got)
    w, wdef = jax.tree.flatten(want)
    assert gdef == wdef
    for a, b in zip(g, w):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("obs", [False, True], ids=["plain", "obs"])
def test_enclosure_carry_equals_stacked_copies(monkeypatch, obs):
    """The broadcast initial carry equals `e` stacked copies leaf by leaf
    (values, dtypes, shapes), and `simulate` at 4 enclosures returns the
    same statistics, rings and obs feed from either."""
    plat, ocfg = platforms.xbof(), obs_m.ObsConfig(enabled=obs)
    for tcfg in (sim._NO_TELEMETRY, sim.SIM_TELEMETRY):
        _assert_trees_equal(sim._enclosure_state(plat, 4, 3, tcfg, ocfg),
                            _stacked_state(plat, 4, 3, tcfg, ocfg))
    wls, arr, cfg = _fleet(4, obs)
    got = sim.simulate(plat, wls, arr, cfg)
    with monkeypatch.context() as m:
        m.setattr(sim, "_enclosure_state", _stacked_state)
        want = sim.simulate(plat, wls, arr, cfg)
    _assert_trees_equal(got, want)
