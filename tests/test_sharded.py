"""Hierarchical (mesh-sharded) serving engine — DESIGN.md §9.

Four layers of guarantees:
  1. With cross-shard exchange DISABLED, the hierarchy is exactly S
     independent engines: the vmap execution matches per-shard single-shard
     runs leaf-for-leaf (stats and state), modulo the global replica-id
     offset in home_of.
  2. The shard_map execution on a real >=n_shards-device mesh matches the
     vmap execution exactly (integer state/stats bitwise, floats to
     reduction-order tolerance) — run under
     XLA_FLAGS=--xla_force_host_platform_device_count=8 (CI tier1-sharded).
  3. With cross-shard exchange ENABLED, the aggregate spare/want exchange
     conserves capacity (Σ granted <= Σ spare, per-shard bounds, no
     self-grant; hypothesis) and the unified LINK_BW byte account keeps its
     per-replica redirect+spill <= budget invariant across shards.
  4. The topology-plane rewire (DESIGN.md §11) reproduces the PR 6
     two-level round BITWISE at depth 2: `hierarchical_exchange` on a flat
     topology equals `shard_exchange` value-for-value, and full engine
     runs match, leaf for leaf, a run with the exchange swapped back to
     the pre-topology `shard_exchange`.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import manager as mgr
from repro.core import topology as topo
from repro.serving import engine as E

jax.config.update("jax_platform_name", "cpu")


def _arrivals(n, hot=((0, 4), (1, 2))):
    a = jnp.zeros((n,), jnp.int32)
    for i, v in hot:
        a = a.at[i].set(v)
    return a


def _run(cfg, arrivals, steps, state=None, step_fn=None):
    state = E.init(cfg, jax.random.key(0)) if state is None else state
    fn = step_fn if step_fn is not None else (
        lambda s, a: E.step(cfg, s, a))
    hist = []
    for _ in range(steps):
        state, stats = fn(state, arrivals)
        hist.append(jax.tree.map(np.asarray, stats))
    return state, hist


class TestHierarchyIsIndependentEnginesWhenCrossOff:
    """Layer 1: n_shards=S with cross_shard=False == S disjoint engines."""

    S, NL, STEPS = 4, 4, 6

    def test_matches_blockdiagonal_single_shard_runs(self):
        big = E.EngineConfig(n_replicas=self.S * self.NL, n_shards=self.S,
                             cross_shard=False, link_pages_per_step=2,
                             trace_driven=True)
        small = big._replace(n_replicas=self.NL, n_shards=1)
        arr = np.zeros((self.S, self.NL), np.int32)
        arr[0, 0], arr[0, 1], arr[2, 1] = 4, 2, 3
        sb, hb = _run(big, jnp.asarray(arr.reshape(-1)), self.STEPS)

        # the same workload through S independent engines
        parts, phist = [], []
        for s in range(self.S):
            st, h = _run(small, jnp.asarray(arr[s]), self.STEPS)
            parts.append(st)
            phist.append(h)

        # per-replica stats concatenate, scalar stats add up
        for t in range(self.STEPS):
            for k in ("util", "link_budget_bytes", "link_redirect_bytes",
                      "link_spill_bytes", "want_pages"):
                np.testing.assert_allclose(
                    hb[t][k],
                    np.concatenate([phist[s][t][k] for s in range(self.S)]),
                    rtol=1e-6, atol=1e-6, err_msg=k)
            for k in ("active", "queued", "redirected", "offsite_pages",
                      "log_commits"):
                assert hb[t][k] == sum(phist[s][t][k] for s in range(self.S)), k
            np.testing.assert_allclose(
                hb[t]["attn_norm"],
                sum(phist[s][t]["attn_norm"] for s in range(self.S)),
                rtol=1e-5)
            assert hb[t]["cross_redirected"] == 0
            assert hb[t]["cross_link_borrowed_bytes"] == 0

        # state: every shard-owned leaf equals the independent engine's,
        # with home ids offset by the shard's global replica base
        for s in range(self.S):
            lo, hi = s * self.NL, (s + 1) * self.NL
            ind = parts[s]
            exp_home = np.asarray(ind.home_of)
            exp_home = np.where(exp_home >= 0, exp_home + lo, exp_home)
            np.testing.assert_array_equal(
                np.asarray(sb.home_of)[lo:hi], exp_home)
            np.testing.assert_array_equal(
                np.asarray(sb.remaining)[lo:hi], np.asarray(ind.remaining))
            np.testing.assert_array_equal(
                np.asarray(sb.queue)[lo:hi], np.asarray(ind.queue))
            for leaf_b, leaf_i in zip(
                    jax.tree.leaves(sb.pool._replace(logs=None)),
                    jax.tree.leaves(ind.pool._replace(logs=None))):
                np.testing.assert_allclose(
                    np.asarray(leaf_b)[lo:hi], np.asarray(leaf_i),
                    rtol=1e-6, atol=1e-6)
            # per-shard WAL counters == the independent pool's scalars
            assert int(np.asarray(sb.pool.logs.commits)[s]) == int(
                np.asarray(ind.pool.logs.commits))


class TestCrossShardExchange:
    """Layer 3a: enabling the exchange moves overflow to idle shards."""

    def test_overflow_exports_to_idle_shard(self):
        cfg = E.EngineConfig(n_replicas=8, n_shards=2, seq_slots=2,
                             shadow_slots=2, cross_shard=True)
        # hammer shard 0 far past its slot capacity; shard 1 idle
        arr = jnp.asarray([6, 6, 6, 6, 0, 0, 0, 0], jnp.int32)
        _, hist = _run(cfg, arr, 6)
        assert sum(h["cross_redirected"] for h in hist) > 0

        off = cfg._replace(cross_shard=False)
        _, hist_off = _run(off, arr, 6)
        assert all(h["cross_redirected"] == 0 for h in hist_off)
        # the exchange strictly reduces global backlog
        assert hist[-1]["queued"] < hist_off[-1]["queued"]

    def test_imported_sequences_homed_to_source_shard(self):
        cfg = E.EngineConfig(n_replicas=8, n_shards=2, seq_slots=2,
                             shadow_slots=2, cross_shard=True)
        arr = jnp.asarray([6, 6, 6, 6, 0, 0, 0, 0], jnp.int32)
        state, hist = _run(cfg, arr, 4)
        assert sum(h["cross_redirected"] for h in hist) > 0
        home = np.asarray(state.home_of)[4:]      # shard 1's replicas
        active = np.asarray(state.pool.seq_active)[4:]
        imported = active & (home >= 0) & (home < 4)
        # at least one sequence hosted on shard 1 is homed in shard 0,
        # attributed at shard granularity (the source shard's base id)
        assert imported.any()
        assert (home[imported] == 0).all()

    def test_metered_link_account_holds_across_shards(self):
        """The per-replica redirect+spill <= budget invariant survives the
        hierarchy: cross-shard command debits and borrowed allowance land
        on the same unified account."""
        cfg = E.EngineConfig(n_replicas=8, n_shards=2, seq_slots=2,
                             shadow_slots=2, pages_per_replica=8,
                             max_pages=8, link_pages_per_step=1,
                             cross_shard=True)
        arr = jnp.asarray([5, 5, 5, 5, 0, 0, 0, 0], jnp.int32)
        _, hist = _run(cfg, arr, 8)
        for h in hist:
            assert (h["link_redirect_bytes"] + h["link_spill_bytes"]
                    <= h["link_budget_bytes"] + 1e-4).all()


class TestShardExchangePrimitive:
    """Layer 3b: conservation properties of the aggregate exchange."""

    def _check(self, spare, want, overhead):
        grants, received = mgr.shard_exchange(
            jnp.asarray(spare, jnp.float32), jnp.asarray(want, jnp.float32),
            overhead=overhead)
        g, r = np.asarray(grants), np.asarray(received)
        assert (g >= -1e-6).all()
        assert (r >= -1e-6).all()
        # netting: no shard both lends and borrows, never to itself
        assert (np.abs(np.diag(g)) < 1e-6).all()
        # per-lender: granted bytes never exceed its net spare
        net_spare = np.maximum(spare - want, 0.0)
        assert (g.sum(axis=1) <= net_spare + 1e-4).all()
        # per-borrower: received never exceeds its net want
        net_want = np.maximum(want - spare, 0.0)
        assert (r <= net_want + 1e-4).all()
        # global: Σ received * (1 + overhead) == Σ granted <= Σ spare
        np.testing.assert_allclose(
            r.sum() * (1.0 + overhead), g.sum(), rtol=1e-5, atol=1e-5)
        assert g.sum() <= spare.sum() + 1e-3

    def test_exhaustive_seeds(self):
        rng = np.random.default_rng(0)
        for _ in range(50):
            s = rng.integers(2, 9)
            spare = (rng.random(s) * 100).astype(np.float32)
            want = (rng.random(s) * 100).astype(np.float32)
            self._check(spare, want, float(rng.random() * 0.2))

    def test_fill_by_rank_distributes_exactly_when_feasible(self):
        cap = jnp.asarray([3, 0, 2, 5], jnp.int32)
        got = np.asarray(mgr.fill_by_rank(cap, jnp.int32(6)))
        assert got.sum() == 6
        assert (got <= np.asarray(cap)).all()
        # over-ask clips at capacity
        got = np.asarray(mgr.fill_by_rank(cap, jnp.int32(99)))
        assert got.sum() == 10


class TestDepth2TopologyParity:
    """Layer 4: the topology plane at depth 2 IS the PR 6 exchange.

    Each case runs the engine twice in-process: once as it stands, and once
    with `topology.hierarchical_exchange` replaced by the pre-topology exchange —
    one `manager.shard_exchange` per rtype over all shards. Every stat of
    every step and every state leaf must match leaf-for-leaf: behavioural
    identity, not approximate parity, and independent of the toolchain
    that compiled it (a pinned hash of float state is not).
    """

    CASES = {
        "unmetered": (dict(n_replicas=8, n_shards=2, seq_slots=2,
                           shadow_slots=2, cross_shard=True),
                      [6, 6, 6, 6, 0, 0, 0, 0]),
        "metered": (dict(n_replicas=8, n_shards=2, seq_slots=2,
                         shadow_slots=2, pages_per_replica=8, max_pages=8,
                         link_pages_per_step=1, cross_shard=True),
                    [5, 5, 5, 5, 0, 0, 0, 0]),
        "metered4": (dict(n_replicas=16, n_shards=4, link_pages_per_step=2,
                          trace_driven=True, cross_shard=True),
                     [4, 2, 0, 0, 0, 3, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]),
    }

    @staticmethod
    def _pre_topology_exchange(spare, want, topology, overheads=None):
        """The pre-topology exchange: ONE shard_exchange over all shards."""
        assert topology == topo.flat(spare.shape[0])
        oh = 0.0 if overheads is None else overheads[0]
        g, r = mgr.shard_exchange(jnp.asarray(spare, jnp.float32),
                                  jnp.asarray(want, jnp.float32), oh)
        return g[None], r[None]

    @staticmethod
    def _trajectory(cfg, arr, step_fn, steps=5):
        """Every stat of every step, then every final state leaf."""
        state = E.init(cfg, jax.random.key(0))
        out = []
        for _ in range(steps):
            state, stats = step_fn(state, jnp.asarray(arr, jnp.int32))
            out += [np.asarray(stats[k]) for k in sorted(stats)]
        return out + [np.asarray(x) for x in jax.tree.leaves(state)]

    @staticmethod
    def _assert_same(got, want):
        assert len(got) == len(want)
        for i, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g, w, err_msg=f"leaf {i}")

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_engine_matches_pr6_digest(self, name, monkeypatch):
        kw, arr = self.CASES[name]
        cfg = E.EngineConfig(**kw)
        now = self._trajectory(cfg, arr, partial(E.step, cfg))
        monkeypatch.setattr(topo, "hierarchical_exchange",
                            self._pre_topology_exchange)
        # a fresh jit, so the patched exchange is what gets traced
        ref = self._trajectory(cfg, arr, jax.jit(partial(E._step_impl, cfg)))
        self._assert_same(now, ref)

    def test_flat_hierarchical_exchange_is_shard_exchange_bitwise(self):
        rng = np.random.default_rng(3)
        for _ in range(30):
            s = int(rng.integers(2, 9))
            spare = (rng.random(s) * 100).astype(np.float32)
            want = (rng.random(s) * 100).astype(np.float32)
            oh = float(rng.random() * 0.3)
            g1, r1 = mgr.shard_exchange(
                jnp.asarray(spare), jnp.asarray(want), oh)
            g2, r2 = topo.hierarchical_exchange(
                spare, want, topo.flat(s), (oh,))
            np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2[0]))
            np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2[0]))

    def test_explicit_single_enclosure_matches_flat(self):
        """shards_per_enclosure == n_shards is the same flat topology —
        the config knob cannot fork the depth-2 code path."""
        kw, arr = self.CASES["metered"]
        flat = E.EngineConfig(**kw)
        one = flat._replace(shards_per_enclosure=2)
        assert E.shard_topology(one) == topo.flat(2)
        self._assert_same(
            self._trajectory(one, arr, partial(E.step, one)),
            self._trajectory(flat, arr, partial(E.step, flat)))


class TestEnclosureGroupedTopology:
    """Depth 3: shards grouped into enclosures settle nearest-first."""

    def _cfg(self, **kw):
        base = dict(n_replicas=16, n_shards=4, seq_slots=2, shadow_slots=2,
                    cross_shard=True, shards_per_enclosure=2)
        base.update(kw)
        return E.EngineConfig(**base)

    def test_overflow_still_exports_and_link_account_holds(self):
        cfg = self._cfg(link_pages_per_step=2)
        arr = jnp.asarray([6] * 4 + [0] * 12, jnp.int32)
        _, hist = _run(cfg, arr, 6)
        assert sum(h["cross_redirected"] for h in hist) > 0
        for h in hist:
            assert (h["link_redirect_bytes"] + h["link_spill_bytes"]
                    <= h["link_budget_bytes"] + 1e-4).all()

    def test_enclosure_local_grants_win_before_fabric(self):
        """One busy shard + an idle sibling in the same enclosure: the
        sibling's capacity covers the overflow at the enclosure level, so
        the fabric level moves nothing."""
        spare = jnp.asarray([0.0, 10.0, 10.0, 10.0], jnp.float32)
        want = jnp.asarray([4.0, 0.0, 0.0, 0.0], jnp.float32)
        g, r = topo.hierarchical_exchange(
            spare, want, topo.two_level(2, 2))
        g = np.asarray(g)
        assert g[0].sum() > 0          # enclosure level settles it
        assert g[1].sum() == 0         # nothing left for the fabric
        np.testing.assert_allclose(np.asarray(r).sum(axis=0)[0], 4.0,
                                   rtol=1e-6)

    def test_bad_enclosure_grouping_rejected(self):
        with pytest.raises(ValueError, match="shards_per_enclosure"):
            E.init(self._cfg(shards_per_enclosure=3), jax.random.key(0))


class TestStatsClassification:
    """ISSUE 9 satellite: every stats key's shard reduction is pinned in
    the obs registry, and `_finish_stats` fails LOUDLY on anything
    off-registry (it used to silently fall through to per-replica
    concat, which is wrong for scalars and sums)."""

    EXPECTED = {
        "util": "concat",
        "want_pages": "concat",
        "link_budget_bytes": "concat",
        "link_redirect_bytes": "concat",
        "link_spill_bytes": "concat",
        "active": "sum",
        "queued": "sum",
        "offsite_pages": "sum",
        "redirected": "sum",
        "attn_norm": "first",
        "log_commits": "first",
        "quant_err_norm": "first",
        "cross_redirected": "first",
        "cross_link_borrowed_bytes": "first",
    }

    def test_every_existing_stat_classification_pinned(self):
        got = {s.name: s.reduce for s in E.ENGINE_METRICS.specs()
               if s.reduce != "none"}
        assert got == self.EXPECTED

    def test_step_emits_exactly_the_registered_stats(self):
        cfg = E.EngineConfig(n_replicas=4)
        state = E.init(cfg, jax.random.key(0))
        _, stats = E.step(cfg, state, _arrivals(4))
        assert sorted(stats) == sorted(self.EXPECTED)

    def test_finish_stats_fails_loudly_on_unregistered(self):
        with pytest.raises(KeyError, match="not registered"):
            E._finish_stats({"totally_new_stat": jnp.zeros((4,))})

    def test_finish_stats_rejects_ring_only_metrics(self):
        with pytest.raises(ValueError, match="ring-only"):
            E._finish_stats({"hbm_pressure": jnp.zeros((4,))})


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    class TestShardExchangeHypothesis:
        pytestmark = pytest.mark.slow

        @given(st.integers(2, 12), st.integers(0, 10_000),
               st.floats(0.0, 0.5))
        @settings(max_examples=50, deadline=None)
        def test_borrowed_bounded_by_spare(self, s, seed, overhead):
            """Property (ISSUE 6): Σ borrowed <= Σ spare for any shard
            count, any spare/want pattern, any hop-overhead tax."""
            rng = np.random.default_rng(seed)
            spare = (rng.random(s) * 50).astype(np.float32)
            want = (rng.random(s) * 50).astype(np.float32)
            TestShardExchangePrimitive()._check(spare, want, float(overhead))
except ImportError:  # hypothesis is a [dev] extra; CI installs it
    pass


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices "
                           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
class TestShardMapParity:
    """Layer 2: shard_map on a real mesh == vmap on one device."""

    INT_STATS = ("active", "queued", "redirected", "offsite_pages",
                 "cross_redirected", "log_commits")

    @pytest.mark.parametrize("cross", [False, True])
    def test_shard_map_matches_vmap(self, cross):
        cfg = E.EngineConfig(n_replicas=16, n_shards=4,
                             link_pages_per_step=2, trace_driven=True,
                             cross_shard=cross)
        arr = _arrivals(16, hot=((0, 4), (1, 2), (5, 3)))
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.sharding import engine_state_shardings
        mesh = make_serving_mesh(4)
        sv = E.init(cfg, jax.random.key(0))
        sm = jax.device_put(E.init(cfg, jax.random.key(0)),
                            engine_state_shardings(cfg, mesh))
        step_sm = E.make_sharded_step(cfg, mesh)
        for _ in range(5):
            sv, stv = E.step(cfg, sv, arr)
            sm, stm = step_sm(sm, arr)
        for k in stv:
            a, b = np.asarray(stv[k]), np.asarray(stm[k])
            if k in self.INT_STATS:
                np.testing.assert_array_equal(a, b, err_msg=k)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                           err_msg=k)
        for leaf_v, leaf_m in zip(jax.tree.leaves(sv), jax.tree.leaves(sm)):
            np.testing.assert_allclose(
                np.asarray(leaf_m), np.asarray(leaf_v),
                rtol=1e-6, atol=1e-6)

    def test_serving_mesh_shape(self):
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(4)
        assert mesh.axis_names == (E.SHARD_AXIS,)
        assert mesh.shape[E.SHARD_AXIS] == 4

    def test_obs_plane_matches_vmap(self):
        """ISSUE 9: metric rings, counter totals and the event log land
        identically under shard_map and vmap — the obs leaves keep the
        canonical leading axis, so the shard split IS the local view."""
        from repro.obs import metrics as obs_m
        cfg = E.EngineConfig(
            n_replicas=16, n_shards=4, link_pages_per_step=2,
            cross_shard=True,
            obs=obs_m.ObsConfig(enabled=True, ring_depth=16,
                                event_capacity=256))
        arr = _arrivals(16, hot=((0, 4), (1, 2), (5, 3)))
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.sharding import engine_state_shardings
        mesh = make_serving_mesh(4)
        sv = E.init(cfg, jax.random.key(0))
        sm = jax.device_put(E.init(cfg, jax.random.key(0)),
                            engine_state_shardings(cfg, mesh))
        step_sm = E.make_sharded_step(cfg, mesh)
        for _ in range(5):
            sv, _ = E.step(cfg, sv, arr)
            sm, _ = step_sm(sm, arr)
        hv, hm = E.obs_history(sv), E.obs_history(sm)
        assert sorted(hv) == sorted(hm)
        for k in hv:
            np.testing.assert_allclose(hv[k], hm[k], rtol=1e-6, atol=1e-6,
                                       err_msg=k)
        tv, tm = E.obs_totals(sv), E.obs_totals(sm)
        for k in tv:
            np.testing.assert_allclose(tv[k], tm[k], rtol=1e-6, atol=1e-6,
                                       err_msg=k)
        ev, dv = E.obs_events(sv)
        em, dm = E.obs_events(sm)
        assert dv == dm == 0
        assert ev == em
