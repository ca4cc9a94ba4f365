"""Invariants of the unified management round (`repro.core.manager`),
parametrized over the consumer styles that share it: the JBOF simulator
(slot-fragmented surplus, multi-round claims, persistent claims), the
serving engine (one proc slot + one DRAM slot, single sweep), the harvest
state machine (persistent claims), and the full XBOF+ registry (PROCESSOR +
DRAM + FLASH_BW + LINK_BW through one round)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import descriptors as d
from repro.core import harvest as hv
from repro.core import manager as mgr
from repro.jbof import platforms
from repro.jbof import sim
from repro.serving import engine

jax.config.update("jax_platform_name", "cpu")

N = 6

SIM_STYLE = mgr.ManagerConfig(n_slots=4, policies=(
    mgr.ResourcePolicy(rtype=d.PROCESSOR, slot0=0, slots=4, claim_rounds=4,
                       watermark=0.75, gate_watermark=0.95,
                       preserve_claims=True, gate_new_only=True),
))
ENGINE_STYLE = mgr.ManagerConfig(n_slots=2, policies=(
    mgr.ResourcePolicy(rtype=d.PROCESSOR, slot0=0, slots=1, claim_rounds=1,
                       watermark=0.75, gate_watermark=0.98),
    mgr.ResourcePolicy(rtype=d.DRAM, slot0=1, slots=1, claim_rounds=0,
                       min_amount=4.0, amount_gated=True),
))
HARVEST_STYLE = mgr.ManagerConfig(n_slots=2, policies=(
    mgr.ResourcePolicy(rtype=d.PROCESSOR, slot0=0, slots=1, claim_rounds=1,
                       max_lenders=1, watermark=0.75, preserve_claims=True),
))
XBOFPLUS_STYLE = mgr.ManagerConfig(n_slots=8, policies=(
    mgr.ResourcePolicy(rtype=d.PROCESSOR, slot0=0, slots=4, claim_rounds=4,
                       watermark=0.75, gate_watermark=0.95,
                       preserve_claims=True, gate_new_only=True),
    mgr.ResourcePolicy(rtype=d.FLASH_BW, slot0=4, slots=2, claim_rounds=4,
                       watermark=0.75, gate_watermark=0.98,
                       preserve_claims=True, gate_new_only=True),
    mgr.ResourcePolicy(rtype=d.LINK_BW, slot0=6, slots=2, claim_rounds=4,
                       watermark=0.75, preserve_claims=True,
                       gate_new_only=True),
))

CONFIGS = [SIM_STYLE, ENGINE_STYLE, HARVEST_STYLE, XBOFPLUS_STYLE]
IDS = ["sim", "engine", "harvest", "xbof+"]

# three proc-bound borrowers, three idle lenders, data-end never busy
PROC = jnp.array([0.95, 0.9, 0.85, 0.2, 0.1, 0.05], jnp.float32)
DATA = jnp.full((N,), 0.3, jnp.float32)
# data-end-bound / link-bound node mix for the new rtypes
FLASH = jnp.array([0.99, 0.97, 0.2, 0.1, 0.96, 0.05], jnp.float32)
LINK = jnp.array([0.9, 0.2, 0.1, 0.85, 0.1, 0.05], jnp.float32)


def _inputs(cfg, proc=PROC, data=DATA):
    rtypes = {pol.rtype for pol in cfg.policies}
    inputs = {d.PROCESSOR: mgr.RoundInputs(util=proc, gate_util=data)}
    if d.DRAM in rtypes:
        inputs[d.DRAM] = mgr.RoundInputs(amount=jnp.full((N,), 8.0))
    if d.FLASH_BW in rtypes:
        inputs[d.FLASH_BW] = mgr.RoundInputs(
            util=FLASH, gate_util=LINK, amount=jnp.maximum(1.0 - FLASH, 0.0))
    if d.LINK_BW in rtypes:
        inputs[d.LINK_BW] = mgr.RoundInputs(
            util=LINK, amount=jnp.maximum(1.0 - LINK, 0.0))
    return inputs


def _round(cfg, proc=PROC, data=DATA, table=None):
    m = mgr.ResourceManager(cfg)
    t = m.init_table(N) if table is None else table
    return m, m.round(t, _inputs(cfg, proc, data))


@pytest.mark.parametrize("cfg", CONFIGS, ids=IDS)
class TestRoundInvariants:
    def test_no_self_lending(self, cfg):
        _, t = _round(cfg)
        bid = np.asarray(t.borrower_id)
        claimed = np.asarray(t.valid) & (bid != d.FREE)
        assert not np.any(claimed & (bid == np.arange(N)[:, None]))

    def test_claims_only_on_valid_descriptors(self, cfg):
        """A withdrawn descriptor drops its claims: no claim may survive on
        an invalid row after the round."""
        m, t = _round(cfg)
        # lenders flip busy -> their descriptors withdraw next round
        proc2 = jnp.full((N,), 0.95, jnp.float32)
        t2 = m.round(t, _inputs(cfg, proc2, DATA))
        bid = np.asarray(t2.borrower_id)
        is_proc = np.asarray(t2.rtype) == d.PROCESSOR
        stale = (~np.asarray(t2.valid)) & is_proc & (bid != d.FREE)
        assert not np.any(stale)

    def test_borrowers_get_lenders(self, cfg):
        _, t = _round(cfg)
        for b in range(3):
            assert bool(jnp.any(d.lenders_of(t, b, d.PROCESSOR))), b

    def test_deterministic_under_ties(self, cfg):
        """Equal utilizations everywhere: `jnp.argsort` ties break stably by
        node id, so repeated rounds produce identical tables and the lowest
        borrower id claims the lowest lender id."""
        proc = jnp.array([0.9, 0.9, 0.9, 0.1, 0.1, 0.1], jnp.float32)
        m, t1 = _round(cfg, proc=proc)
        _, t2 = _round(cfg, proc=proc)
        for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
            assert bool((jnp.asarray(a) == jnp.asarray(b)).all())
        # stable tie-break: borrower 0 claimed node 3 (first idle lender)
        assert bool(d.lenders_of(t1, 0, d.PROCESSOR)[3])

    def test_assist_matrix_rows_sum_le_one(self, cfg):
        m, t = _round(cfg)
        M = np.asarray(m.assist_matrix(t, d.PROCESSOR))
        assert M.shape == (N, N)
        assert (M >= 0).all() and (M.sum(axis=1) <= 1.0 + 1e-6).all()
        # pledges exist exactly where claims exist
        assert M.sum() > 0

    def test_lender_cap_respected(self, cfg):
        """No borrower holds more lenders than the config's cap."""
        proc = jnp.array([0.99, 0.1, 0.1, 0.1, 0.1, 0.1], jnp.float32)
        m, t = _round(cfg, proc=proc)
        n_lenders = int(jnp.sum(d.lenders_of(t, 0, d.PROCESSOR)))
        assert n_lenders <= cfg.policy(d.PROCESSOR).lender_cap
        assert n_lenders >= 1


class TestLenderCapSemantics:
    """Pin `_claim_sweeps`' cap accounting: ``lender_cap`` bounds DISTINCT
    lender nodes (the any-slot `lenders_of` reduction), while total claimed
    slots are bounded by ``claim_rounds`` — a lender publishing multiple
    slots must not let a borrower exceed either bound."""

    CFG = mgr.ManagerConfig(n_slots=4, policies=(
        mgr.ResourcePolicy(rtype=d.PROCESSOR, slot0=0, slots=4,
                           claim_rounds=4, max_lenders=2, watermark=0.75,
                           preserve_claims=True),))

    def test_lender_cap_counts_distinct_lenders_not_slots(self):
        """One starved borrower, three idle multi-slot lenders: claims may
        deepen into one lender's fragmented slots without consuming cap,
        but distinct lenders never exceed max_lenders — even across rounds
        with persistent claims."""
        m = mgr.ResourceManager(self.CFG)
        proc = jnp.array([0.99, 0.1, 0.1, 0.1], jnp.float32)
        data = jnp.full((4,), 0.2, jnp.float32)
        t = m.init_table(4)
        for _ in range(3):  # persistent claims accumulate across rounds
            t = m.round(t, {d.PROCESSOR: mgr.RoundInputs(util=proc,
                                                         gate_util=data)})
            distinct = int(jnp.sum(d.lenders_of(t, 0, d.PROCESSOR)))
            assert 1 <= distinct <= 2       # max_lenders bound, always
        # ties break to the lowest flat index, so the first round deepens
        # into lender 1's fragmented slots: multi-slot claims on ONE lender
        # are the fragmentation feature, not a cap leak
        slots_claimed = int(jnp.sum(t.borrower_id == 0))
        assert slots_claimed >= 3           # deepened past one slot/lender
        assert slots_claimed <= 3 * 4       # <= claim_rounds per round

    def test_at_cap_no_further_acquisition(self):
        """A borrower holding max_lenders distinct lenders claims nothing
        more, even with free descriptors remaining."""
        m = mgr.ResourceManager(self.CFG)
        proc = jnp.array([0.99, 0.1, 0.1, 0.1], jnp.float32)
        data = jnp.full((4,), 0.2, jnp.float32)
        t = m.init_table(4)
        for _ in range(4):
            t = m.round(t, {d.PROCESSOR: mgr.RoundInputs(util=proc,
                                                         gate_util=data)})
        assert int(jnp.sum(d.lenders_of(t, 0, d.PROCESSOR))) == 2
        # free descriptors remain on the third idle lender
        free = np.asarray(t.valid) & (np.asarray(t.borrower_id) == d.FREE)
        assert free.any()


class TestConsumerParity:
    def test_harvest_wrapper_preserves_claims_across_rounds(self):
        """`apply_processor_round` (now a manager wrapper) keeps a claim
        alive while borrower and lender still qualify."""
        t = d.make_table(4, 2)
        proc = jnp.array([0.9, 0.1, 0.5, 0.5], jnp.float32)
        data = jnp.full((4,), 0.2, jnp.float32)
        t = hv.apply_processor_round(t, proc, data)
        assert int(t.borrower_id[1, 0]) == 0
        t = hv.apply_processor_round(t, proc, data)
        assert int(t.borrower_id[1, 0]) == 0  # claim persisted, not re-made
        # borrower recovers -> claim released
        proc2 = jnp.array([0.2, 0.1, 0.5, 0.5], jnp.float32)
        t = hv.apply_processor_round(t, proc2, data)
        assert int(t.borrower_id[1, 0]) == d.FREE

    def test_engine_style_publishes_dram_slot(self):
        m = mgr.ResourceManager(ENGINE_STYLE)
        t = m.init_table(N)
        dram = jnp.array([8.0, 2.0, 8.0, 8.0, 0.0, 8.0], jnp.float32)
        inputs = _inputs(ENGINE_STYLE)
        inputs[d.DRAM] = mgr.RoundInputs(amount=dram)
        t = m.round(t, inputs)
        v = np.asarray(t.valid[:, 1])
        assert v.tolist() == [True, False, True, True, False, True]
        assert np.asarray(t.rtype[:, 1] == d.DRAM)[v].all()

    def test_sim_style_fragments_all_slots(self):
        m = mgr.ResourceManager(SIM_STYLE)
        t = m.init_table(N)
        t = m.round(t, _inputs(SIM_STYLE))
        lend_rows = np.asarray(t.valid[3:])  # idle nodes lend
        assert lend_rows.all()               # every slot fragmented
        busy_rows = np.asarray(t.valid[:3])
        assert not busy_rows.any()

    def test_multi_round_claims_accumulate(self):
        """SIM_STYLE's claim_rounds sweeps let one starved borrower harvest
        several lenders, deterministically busiest-first."""
        proc = jnp.array([0.99, 0.98, 0.1, 0.1, 0.1, 0.1], jnp.float32)
        m, t = _round(SIM_STYLE, proc=proc)
        n0 = int(jnp.sum(d.lenders_of(t, 0, d.PROCESSOR)))
        n1 = int(jnp.sum(d.lenders_of(t, 1, d.PROCESSOR)))
        assert n0 >= 2 and n1 >= 1


class TestResourceRegistry:
    """FLASH_BW and LINK_BW are one `ResourceSpec` + one `ResourcePolicy`
    each — the same round publishes, claims and syncs them."""

    def test_flash_and_link_claims_flow_through_round(self):
        m, t = _round(XBOFPLUS_STYLE)
        # flash-bound nodes 0, 1 (and 4) harvested idle backbones
        for b in (0, 1):
            assert bool(jnp.any(d.lenders_of(t, b, d.FLASH_BW))), b
        # link-bound nodes 0, 3 harvested idle ports
        for b in (0, 3):
            assert bool(jnp.any(d.lenders_of(t, b, d.LINK_BW))), b
        Mf = np.asarray(m.assist_matrix(t, d.FLASH_BW))
        Ml = np.asarray(m.assist_matrix(t, d.LINK_BW))
        assert Mf.sum() > 0 and Ml.sum() > 0
        for M in (Mf, Ml):
            assert (M.sum(axis=1) <= 1.0 + 1e-6).all()
            assert (np.diag(M) == 0).all()

    def test_rtypes_do_not_cross_claim(self):
        """A FLASH_BW claim never lands on a PROCESSOR/LINK_BW descriptor:
        slot ranges and rtype masks stay disjoint through the round."""
        _, t = _round(XBOFPLUS_STYLE)
        rt = np.asarray(t.rtype)
        assert set(rt[:, :4].flatten()) == {d.PROCESSOR}
        assert set(rt[:, 4:6].flatten()) == {d.FLASH_BW}
        assert set(rt[:, 6:].flatten()) == {d.LINK_BW}

    def test_claim_best_scores_high_amount_for_capacity_rtypes(self):
        """Regression for the old two-way `jnp.where` score: any rtype >= 2
        was scored with the DRAM branch only by accident. The registry
        weights now drive the score: FLASH_BW prefers the largest published
        amount."""
        t = d.make_table(4, 2)
        t = d.publish(t, 1, 0, d.FLASH_BW, 0.2)
        t = d.publish(t, 2, 0, d.FLASH_BW, 0.9)
        t, lender, _, ok = d.claim_best(t, 0, d.FLASH_BW)
        assert bool(ok) and int(lender) == 2

    def test_claim_best_scores_idle_lender_for_processor(self):
        t = d.make_table(4, 2)
        t = d.publish(t, 1, 0, d.PROCESSOR, 0.0, 0.10)
        t = d.publish(t, 2, 0, d.PROCESSOR, 0.0, 0.30)
        t, lender, _, ok = d.claim_best(t, 0, d.PROCESSOR)
        assert bool(ok) and int(lender) == 1

    def test_sync_refreshes_capacity_amounts(self):
        """Regression: sync used to touch only PROCESSOR descriptors,
        leaving DRAM/FLASH_BW/LINK_BW amount_a stale after grants. The
        registry's "amount" sync rule refreshes them every round."""
        m = mgr.ResourceManager(XBOFPLUS_STYLE)
        t = m.round(m.init_table(N), _inputs(XBOFPLUS_STYLE))
        shrunk = jnp.full((N,), 0.01, jnp.float32)
        inputs = _inputs(XBOFPLUS_STYLE)
        inputs[d.FLASH_BW] = inputs[d.FLASH_BW]._replace(amount=shrunk)
        inputs[d.LINK_BW] = inputs[d.LINK_BW]._replace(amount=shrunk)
        t = m.round(t, inputs)
        for rtype in (d.FLASH_BW, d.LINK_BW):
            is_r = np.asarray(t.rtype) == rtype
            live = is_r & np.asarray(t.valid)
            assert live.any()
            np.testing.assert_allclose(
                np.asarray(t.amount_a)[live], 0.01, atol=1e-6)

    def test_sync_refreshes_dram_amount_after_grant(self):
        """Engine-style DRAM descriptor follows the current free-page count
        instead of the value at publish time."""
        m = mgr.ResourceManager(ENGINE_STYLE)
        inputs = _inputs(ENGINE_STYLE)
        inputs[d.DRAM] = mgr.RoundInputs(amount=jnp.full((N,), 32.0))
        t = m.round(m.init_table(N), inputs)
        assert float(t.amount_a[3, 1]) == 32.0
        inputs[d.DRAM] = mgr.RoundInputs(amount=jnp.full((N,), 9.0))
        t = m.round(t, inputs)
        assert float(t.amount_a[3, 1]) == 9.0

    def test_slot_mask_locates_policy_slots(self):
        """Consumers find a policy's descriptors via `slot_mask`, not
        hardcoded indices (regression: the engine read `table[:, 1]` for
        DRAM, which breaks silently if a policy is inserted before it)."""
        m = mgr.ResourceManager(XBOFPLUS_STYLE)
        assert np.asarray(m.slot_mask(d.PROCESSOR)).tolist() == \
            [True] * 4 + [False] * 4
        assert np.asarray(m.slot_mask(d.FLASH_BW)).tolist() == \
            [False] * 4 + [True] * 2 + [False] * 2
        assert np.asarray(m.slot_mask(d.LINK_BW, 8)).tolist() == \
            [False] * 6 + [True] * 2
        e = mgr.ResourceManager(ENGINE_STYLE)
        assert np.asarray(e.slot_mask(d.DRAM)).tolist() == [False, True]
        with pytest.raises(KeyError):
            e.slot_mask(d.FLASH_BW)

    def test_custom_rtype_registers_and_claims(self):
        """Adding a resource type is one register() + one policy entry."""
        rt = 7
        d.register(d.ResourceSpec(rt, "test_bw", score_a=1.0, sync_a="amount"))
        try:
            cfg = mgr.ManagerConfig(n_slots=1, policies=(
                mgr.ResourcePolicy(rtype=rt, slot0=0, slots=1,
                                   claim_rounds=1),))
            m = mgr.ResourceManager(cfg)
            util = jnp.array([0.9, 0.1, 0.1], jnp.float32)
            amt = jnp.array([0.0, 3.0, 5.0], jnp.float32)
            t = m.round(m.init_table(3),
                        {rt: mgr.RoundInputs(util=util, amount=amt)})
            lenders = np.asarray(d.lenders_of(t, 0, rt))
            assert lenders[2] and not lenders[1]  # highest amount wins
        finally:
            del d.REGISTRY[rt]

    def test_gate_new_only_retains_claims_under_gate(self):
        """The futility gate vetoes new claims but does not release live
        ones while the borrower stays busy — the stabilizer that lets two
        harvestable rtypes gate on each other without 2-cycling."""
        cfg = mgr.ManagerConfig(n_slots=2, policies=(
            mgr.ResourcePolicy(rtype=d.PROCESSOR, slot0=0, slots=2,
                               claim_rounds=1, watermark=0.75,
                               gate_watermark=0.95, preserve_claims=True,
                               gate_new_only=True),))
        m = mgr.ResourceManager(cfg)
        proc = jnp.array([0.9, 0.1, 0.1], jnp.float32)
        calm = jnp.full((3,), 0.2, jnp.float32)
        busy = jnp.full((3,), 0.99, jnp.float32)
        t = m.round(m.init_table(3),
                    {d.PROCESSOR: mgr.RoundInputs(util=proc, gate_util=calm)})
        assert bool(jnp.any(d.lenders_of(t, 0, d.PROCESSOR)))
        # gate trips (data-end exhausted): claim is retained, not re-made
        t = m.round(t, {d.PROCESSOR: mgr.RoundInputs(util=proc, gate_util=busy)})
        assert bool(jnp.any(d.lenders_of(t, 0, d.PROCESSOR)))
        # borrower recovers: claim released even though gate still trips
        calm_proc = jnp.array([0.1, 0.1, 0.1], jnp.float32)
        t = m.round(t, {d.PROCESSOR: mgr.RoundInputs(util=calm_proc,
                                                     gate_util=busy)})
        assert not bool(jnp.any(d.lenders_of(t, 0, d.PROCESSOR)))


class TestFillByRank:
    """`fill_by_rank` is the integer-grant distribution step of the
    hierarchical round: every shard computes it on replicated inputs, so
    it must be a deterministic pure function with exact conservation."""

    def test_deterministic(self):
        cap = jnp.array([3, 0, 5, 2, 7], jnp.int32)
        a = mgr.fill_by_rank(cap, 9)
        b = mgr.fill_by_rank(cap, 9)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), [3, 0, 5, 1, 0])

    def test_conservation_sum_is_min_of_capacity_and_total(self):
        rng = np.random.default_rng(7)
        for _ in range(50):
            n = int(rng.integers(1, 12))
            cap = jnp.asarray(rng.integers(0, 9, n), jnp.int32)
            total = int(rng.integers(0, 40))
            out = np.asarray(mgr.fill_by_rank(cap, total))
            assert out.sum() == min(int(np.asarray(cap).sum()), total)
            assert (out >= 0).all()
            assert (out <= np.asarray(cap)).all()

    def test_order_stability_under_permutation(self):
        """Permuting the capacity vector permutes nothing else: each
        node's fill depends only on the capacity mass ranked BEFORE it,
        so the fill of the prefix is invariant — shards disagreeing on
        ordering would silently double-grant."""
        rng = np.random.default_rng(11)
        cap = rng.integers(0, 9, 8)
        total = 17
        base = np.asarray(mgr.fill_by_rank(jnp.asarray(cap), total))
        for _ in range(20):
            perm = rng.permutation(8)
            out = np.asarray(mgr.fill_by_rank(jnp.asarray(cap[perm]), total))
            # the same node can receive a different share under a
            # different rank, but the aggregate and the fill-prefix
            # structure are permutation-stable:
            assert out.sum() == base.sum()
            # prefix property: once any node is left short, every node
            # ranked after it gets exactly zero
            short = np.flatnonzero(out < cap[perm])
            if short.size:
                assert (out[short[0] + 1:] == 0).all()

    def test_float_capacities_and_jit(self):
        cap = jnp.array([0.5, 1.25, 2.0], jnp.float32)
        out = np.asarray(jax.jit(mgr.fill_by_rank)(cap, 2.0))
        np.testing.assert_allclose(out, [0.5, 1.25, 0.25], rtol=1e-6)


def _ref_sweeps(pol, valid, rtype, bid, amount_a, amount_b, util, borrow):
    """The claim sweeps of one policy as plain Python loops, in place on
    ``bid``: busiest first with ties by node id, one claim per borrower per
    sweep while it holds under ``lender_cap`` distinct lenders, the best
    free offer not its own, ties to the lowest flat index."""
    n, s = valid.shape
    spec = d.spec_of(pol.rtype)
    wa, wb = np.float32(spec.score_a), np.float32(spec.score_b)
    offers = [(i, j) for i in range(n) for j in range(s)
              if valid[i, j] and rtype[i, j] == pol.rtype]
    order = sorted(range(n), key=lambda i: (-util[i], i))
    for _ in range(pol.claim_rounds):
        for b in order:
            have = len({i for i, j in offers if bid[i, j] == b})
            if not borrow[b] or have >= pol.lender_cap:
                continue
            best = None
            for i, j in offers:                             # flat order
                if bid[i, j] != d.FREE or i == b:
                    continue
                score = wa * amount_a[i, j] + wb * amount_b[i, j]
                if best is None or score > best[0]:
                    best = (score, i, j)
            if best is not None:
                bid[best[1], best[2]] = b


def _ref_round(cfg, table, inputs):
    """Plain sequential restatement of `ResourceManager.round`'s descriptor
    decisions (trigger, publish, release, claim sweeps) in NumPy and
    Python loops. Returns ``(valid, rtype, borrower_id)``."""
    valid, rtype, bid, amount_a, amount_b = (
        np.array(x) for x in (table.valid, table.rtype, table.borrower_id,
                              table.amount_a, table.amount_b))
    n, s = valid.shape
    for pol in cfg.policies:
        inp = inputs[pol.rtype]
        util = np.asarray(inp.util, np.float32)
        gate = (np.zeros(n, np.float32) if inp.gate_util is None
                else np.asarray(inp.gate_util, np.float32))
        amount = (None if inp.amount is None
                  else np.asarray(inp.amount, np.float32))
        busy = util > pol.watermark
        if pol.amount_gated:
            lend, borrow = amount > pol.min_amount, np.zeros(n, bool)
            keep = borrow
        else:
            gw = pol.watermark if pol.gate_watermark is None \
                else pol.gate_watermark
            lend, borrow = ~busy, busy & ~(gate > gw)
            keep = busy if pol.gate_new_only else borrow
            if amount is not None and pol.min_amount > 0.0:
                lend = lend & (amount > pol.min_amount)
        for i in range(n):                                  # publish
            for j in range(pol.slot0, pol.slot0 + pol.slots):
                if not pol.preserve_claims or (
                        not lend[i] and rtype[i, j] == pol.rtype):
                    bid[i, j] = d.FREE
                valid[i, j], rtype[i, j] = lend[i], pol.rtype
                if amount is not None:
                    amount_a[i, j] = amount[i]
                amount_b[i, j] = util[i]
        if pol.preserve_claims:                             # release
            for i in range(n):
                for j in range(s):
                    b = bid[i, j]
                    if b != d.FREE and rtype[i, j] == pol.rtype \
                            and not keep[b]:
                        bid[i, j] = d.FREE
        _ref_sweeps(pol, valid, rtype, bid, amount_a, amount_b, util, borrow)
    return valid, rtype, bid


REF_CFGS = {
    "sim-xbof": sim._manager(platforms.xbof()).cfg,
    "sim-xbof+": sim._manager(platforms.xbof_full()).cfg,
    "engine": engine._manager(engine.EngineConfig(
        link_pages_per_step=2)).cfg,
}


def _tie_heavy_inputs(cfg, rng, lead):
    """Utilizations and amounts on coarse grids (multiples of 1/8 and
    1/4), so busiest-first order and claim scores tie often."""
    grid = lambda hi, step: jnp.asarray(                         # noqa: E731
        rng.integers(0, int(hi / step) + 1, lead) * step, jnp.float32)
    inputs = {}
    for pol in cfg.policies:
        amount = None if pol.rtype == d.PROCESSOR else grid(4.0, 0.25)
        inputs[pol.rtype] = mgr.RoundInputs(
            util=grid(1.25, 0.125), gate_util=grid(1.0, 0.125),
            amount=amount)
    return inputs


@pytest.mark.parametrize("vmapped", [False, True], ids=["one", "vmapped"])
@pytest.mark.parametrize("name", sorted(REF_CFGS))
def test_round_matches_sequential_reference(name, vmapped):
    """The round's claims equal the plain sequential sweep's, bit for bit:
    busiest first with ties by node id, own node excluded, at most one
    claim per borrower per sweep, ``lender_cap`` distinct lenders, per-rtype
    scores, ties to the lowest flat index. Consecutive rounds on seeded
    tie-heavy inputs, claims standing across rounds where kept."""
    cfg = REF_CFGS[name]
    m = mgr.ResourceManager(cfg)
    n_enc, n = 3, 16
    rng = np.random.default_rng(sorted(REF_CFGS).index(name))
    lead = (n_enc, n) if vmapped else (n,)
    table = m.init_table(n)
    if vmapped:
        table = jax.tree.map(lambda a: jnp.stack([a] * n_enc), table)
    rnd = jax.jit(jax.vmap(m.round) if vmapped else m.round)
    claims = 0
    for _ in range(5):
        inputs = _tie_heavy_inputs(cfg, rng, lead)
        new = rnd(table, inputs)
        for e in range(n_enc if vmapped else 1):
            pick = (lambda a: a[e]) if vmapped else (lambda a: a)  # noqa: E731
            got = (np.asarray(pick(new.valid)), np.asarray(pick(new.rtype)),
                   np.asarray(pick(new.borrower_id)))
            want = _ref_round(cfg, jax.tree.map(pick, table),
                              jax.tree.map(pick, inputs))
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
            claims += int((got[2] != d.FREE).sum())
        table = new
    assert claims > 0


@pytest.mark.parametrize("name", sorted(REF_CFGS))
def test_claim_sweeps_match_reference_on_random_tables(name):
    """The sweeps alone on arbitrary tables, where a borrower may also hold
    offers of its own and standing claims (which a round's triggers never
    produce together): own offers are skipped and standing claims count
    against ``lender_cap``."""
    cfg = REF_CFGS[name]
    m = mgr.ResourceManager(cfg)
    rng = np.random.default_rng(100 + sorted(REF_CFGS).index(name))
    n, s = 16, cfg.n_slots
    sweeps = jax.jit(m._claim_sweeps, static_argnums=1)
    for pol in (p for p in cfg.policies if p.claim_rounds > 0):
        for _ in range(4):
            table = d.IdleResourceTable(
                valid=jnp.asarray(rng.random((n, s)) < 0.7),
                rtype=jnp.asarray(rng.choice(
                    [p.rtype for p in cfg.policies], (n, s)), jnp.int8),
                borrower_id=jnp.asarray(np.where(
                    rng.random((n, s)) < 0.2, rng.integers(0, n, (n, s)),
                    d.FREE), jnp.int32),
                amount_a=jnp.asarray(rng.integers(0, 5, (n, s)) / 4,
                                     jnp.float32),
                amount_b=jnp.asarray(rng.integers(0, 9, (n, s)) / 8,
                                     jnp.float32),
                info_a=jnp.zeros((n, s), jnp.int32),
                info_b=jnp.zeros((n, s), jnp.int32))
            util = jnp.asarray(rng.integers(0, 9, n) / 8, jnp.float32)
            borrow = jnp.asarray(rng.random(n) < 0.5)
            got = sweeps(table, pol, util, borrow)
            want = np.array(table.borrower_id)
            _ref_sweeps(pol, np.asarray(table.valid), np.asarray(table.rtype),
                        want, np.asarray(table.amount_a),
                        np.asarray(table.amount_b), np.asarray(util),
                        np.asarray(borrow))
            np.testing.assert_array_equal(np.asarray(got.borrower_id), want)
            for f in ("valid", "rtype", "amount_a", "amount_b", "info_a",
                      "info_b"):
                np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                              np.asarray(getattr(table, f)))


def test_claim_sweep_has_no_index_ops():
    """The claim sweep's node step reads and writes the descriptor table
    by compares, never by gather or scatter: under vmap over enclosures a
    gather there was 94% of the simulator's device time on a TPU v5e."""
    m = sim._manager(platforms.xbof_full())
    n_enc, n = 2, 8

    def rnd(table, util):
        return m.round(table, {p.rtype: mgr.RoundInputs(
            util=util, gate_util=util / 2, amount=1.0 - util)
            for p in m.cfg.policies})

    table = jax.tree.map(lambda a: jnp.stack([a] * n_enc), m.init_table(n))
    util = jnp.zeros((n_enc, n), jnp.float32)
    hlo = jax.jit(jax.vmap(rnd)).lower(table, util).compile().as_text()
    ops = re.findall(r"= \S+ (gather|scatter)\(.*?op_name=\"([^\"]*)\"", hlo)
    assert "claim_sweep" in hlo
    assert not [op for op in ops if "claim_sweep" in op[1]]
