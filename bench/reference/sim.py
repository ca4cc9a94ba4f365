"""Plain reference of the JBOF fluid simulator on the XBOF platform.

A straightforward, loop-over-windows NumPy restatement of the simulated
semantics (arXiv:2509.10251 §4.3-§4.6, §5.1): per 1 ms window and per SSD,
queued bytes turn into compute-end, data-end, link and host time demands;
every `mgmt_interval` windows each enclosure runs the descriptor round
(quadrant triggers, publish, release, busiest-first claim sweeps, sync) for
processor clocks and DRAM segments; the enclosures' residual (spare, want)
settle once per interval across the fabric; the feasible fraction of the
backlog is served and the rest carries over.

It imports nothing of the program under test. The model's constants are the
paper's (Table 1, §4.6, §5.3), restated here; the platform and workloads
come from the configuration and traffic files. Arithmetic is float64;
``cast`` may put the state and inputs in another array type (the control
runs it in bfloat16).

Covered: harvesting of processor clocks and DRAM segments with fabric
federation, static parametric miss-ratio curves, no failure events, no
flash/link harvesting, no VH, no open-channel host firmware.
"""
from __future__ import annotations

import numpy as np

# ---- SSD model constants (paper Table 1, §4.6, §5.3 calibration)
PAGE_BYTES = 16 * 1024
SLICE_BYTES = 4 * 1024
PEAK_READ_BPS = 14e9
PEAK_WRITE_BPS = 10e9
T_READ_AVG = 45e-6
T_READ_LSB = 30e-6
F_READ_PAGES = PEAK_READ_BPS / PAGE_BYTES
F_PROG_PAGES = PEAK_WRITE_BPS / PAGE_BYTES
SLC_AMP_SMALL_WRITE = 2.0
CHANNEL_BPS = 2.4e9                       # one of 8 channels
CLOCK_HZ = 1.0e9
C_PARSE = 600.0
C_READ_SLICE = 1901.0
C_WRITE_SLICE = 882.0
C_MISS_EXTRA = 500.0
DRAM_LOOKUP_S = 100e-9
SEGMENT_BYTES = 2 * 1024 * 1024
SEGMENTS_FULL = int(4.0e12 / (SEGMENT_BYTES // 4 * SLICE_BYTES))
HOST_CLOCK_HZ = 2.1e9
HOST_CLOCKS_PER_S = 16 * HOST_CLOCK_HZ
C_HOST_DRIVER = 1500.0
CXL_BPS_PER_SSD = 16e9
T_LOG_COMMIT = 321.9e-9
CMD_BYTES = 64.0
T_HOST_STACK = 5e-6
T_HOST_SSD_CMD = 1e-6
DRAM_MIN_KEEP_SEGMENTS = 16.0
TARGET_MISS = 0.10
SSD_CAPACITY_TB = 4.0
EPS = 1e-9
FREE = 0xFF
MAX_OVH = 1e3

# descriptor resource types and their claim scores (§4.3): a processor
# borrower prefers the most idle lender, a DRAM borrower the largest amount
PROCESSOR, DRAM = 0, 1


class _Table:
    """Descriptor table of every enclosure: arrays [E, nodes, slots]."""

    def __init__(self, e, n, s, cast):
        self.valid = np.zeros((e, n, s), bool)
        self.rtype = np.zeros((e, n, s), np.int8)
        self.bid = np.full((e, n, s), FREE, np.int64)
        self.amount_a = cast(np.zeros((e, n, s)))
        self.amount_b = cast(np.zeros((e, n, s)))


def float64(x):
    return np.asarray(x, np.float64)


def miss_ratio(wv, cache_frac):
    warm = (1.0 + cache_frac / wv["mrc_c0"]) ** (-wv["mrc_beta"])
    return np.clip(wv["mrc_cold"] + (1.0 - wv["mrc_cold"]) * warm, 0.0, 1.0)


def want_fraction(wv):
    """Smallest cache fraction (on a 33-point grid) whose per-lookup miss
    rate is under the §4.5 target; 1.0 when none is."""
    grid = np.linspace(0.0, 1.0, 33)
    out = np.ones(wv["locality"].shape)
    for c in grid[::-1]:
        ok = miss_ratio(wv, np.full(out.shape, c)) * wv["locality"] \
            <= TARGET_MISS
        out = np.where(ok, c, out)
    return out


def workload_vectors(rows):
    """Per-SSD workload parameters from traffic rows (dicts)."""
    g = lambda f: np.array([f(w) for w in rows], np.float64)
    return {
        "rb": g(lambda w: max(w["read_kb"], 0.1) * 1024.0),
        "wb": g(lambda w: max(w["write_kb"], 0.1) * 1024.0),
        "qd": g(lambda w: w["qd"]),
        "locality": g(lambda w: min(max(w["locality"], 1.0 / 4096.0), 1.0)),
        "mrc_c0": g(lambda w: w["mrc_c0"]),
        "mrc_beta": g(lambda w: w["mrc_beta"]),
        "mrc_cold": g(lambda w: w["mrc_cold"]),
    }


def _pool_share(per_node, cap):
    """Distribute an enclosure-level grant over its nodes ∝ per_node."""
    pool = np.sum(per_node, axis=1, keepdims=True)
    take = np.minimum(cap[:, None], pool)
    return per_node * take / np.maximum(pool, EPS)


def _fluid(assist, surplus, deficit, overhead):
    """Pledged lender capacity -> received capacity and lender draw.
    assist [E, lender, borrower]."""
    pledged = assist * surplus[:, :, None]
    gross = np.sum(pledged, axis=1)
    used = np.minimum(gross / (1.0 + overhead), deficit)
    draw = np.where(gross > 0,
                    used * (1.0 + overhead) / np.maximum(gross, EPS), 0.0)
    return used, pledged * draw[:, None, :]


def _busy_split(work, cap, assist_in, used_from):
    remote = np.clip(work - cap, 0.0, assist_in)
    own = np.clip(work - remote, 0.0, cap)
    usage = np.where(assist_in > 0, remote / np.maximum(assist_in, EPS), 0.0)
    out = np.einsum("elb,eb->el", used_from, usage)
    return own, remote, out


def _assist(tbl, rtype, slots):
    e, n, s = tbl.valid.shape
    claimed = tbl.valid & (tbl.bid != FREE) & (tbl.rtype == rtype)
    m = np.zeros((e, n, n))
    b = np.clip(tbl.bid, 0, n - 1)
    ei, li, si = np.nonzero(claimed)
    np.add.at(m, (ei, li, b[ei, li, si]), 1.0)
    return m / float(slots)


def _round(tbl, pols, inputs):
    """One management round in every enclosure (§4.3-§4.5)."""
    e, n, s = tbl.valid.shape
    ar_e = np.arange(e)
    node_ids = np.arange(n)
    for pol in pols:
        util, gate, amount = inputs[pol["rtype"]]
        rt = pol["rtype"]
        own_busy = util > pol["watermark"]
        lend = ~own_busy
        borrow = own_busy & ~(gate > pol["gate_watermark"])
        keep = own_busy
        if amount is not None and pol["min_amount"] > 0.0:
            lend = lend & (amount > pol["min_amount"])
        # publish; claims on a withdrawn descriptor drop, live ones stay
        sel = np.zeros(s, bool)
        sel[pol["slot0"]:pol["slot0"] + pol["slots"]] = True
        sel = np.broadcast_to(sel[None, None, :], (e, n, s))
        drop = sel & ~lend[:, :, None] & (tbl.rtype == rt)
        tbl.bid = np.where(drop, FREE, tbl.bid)
        if amount is not None:
            tbl.amount_a = np.where(sel, amount[:, :, None], tbl.amount_a)
        tbl.valid = np.where(sel, lend[:, :, None], tbl.valid)
        tbl.rtype = np.where(sel, np.int8(rt), tbl.rtype)
        tbl.amount_b = np.where(sel, util[:, :, None], tbl.amount_b)
        # claims of borrowers that stopped being busy release
        mine = (tbl.bid != FREE) & (tbl.rtype == rt)
        kept = np.take_along_axis(
            keep, np.clip(tbl.bid, 0, n - 1).reshape(e, -1), axis=1
        ).reshape(e, n, s)
        tbl.bid = np.where(~mine | kept, tbl.bid, FREE)
        # claim sweeps: busiest borrower first, one lender per sweep; equal
        # utilizations (to 1e-9, e.g. two saturated nodes) keep node order
        order = np.argsort(-np.round(np.asarray(util, np.float64), 9),
                           axis=1, kind="stable")
        for _ in range(pol["claim_rounds"]):
            for k in range(n):
                node = order[:, k]
                want = borrow[ar_e, node]
                mine_l = (tbl.valid & (tbl.bid == node[:, None, None])
                          & (tbl.rtype == rt)).any(axis=2)
                have = mine_l.sum(axis=1)
                mask = (tbl.valid & (tbl.bid == FREE) & (tbl.rtype == rt)
                        & (node_ids[None, :, None] != node[:, None, None]))
                score = (-tbl.amount_b if rt == PROCESSOR else tbl.amount_a)
                score = np.where(mask, np.asarray(score, np.float64),
                                 -np.inf).reshape(e, -1)
                flat = np.argmax(score, axis=1)
                ok = want & mask.reshape(e, -1).any(axis=1) \
                    & (have < pol["lender_cap"])
                li, si = flat // s, flat % s
                cur = tbl.bid[ar_e, li, si]
                tbl.bid[ar_e, li, si] = np.where(ok, node, cur)
    # sync: processor descriptors carry lender and claimant utilization,
    # DRAM descriptors the lender's current lendable amount
    util_p = inputs[PROCESSOR][0]
    is_p = (tbl.rtype == PROCESSOR) & tbl.valid
    claimed = tbl.bid != FREE
    tbl.amount_b = np.where(is_p, util_p[:, :, None], tbl.amount_b)
    cu = np.take_along_axis(util_p, np.clip(tbl.bid, 0, n - 1).reshape(e, -1),
                            axis=1).reshape(e, n, s)
    tbl.amount_a = np.where(is_p & claimed, cu, tbl.amount_a)
    amt = inputs[DRAM][2]
    is_d = (tbl.rtype == DRAM) & tbl.valid
    tbl.amount_a = np.where(is_d, amt[:, :, None], tbl.amount_a)
    return tbl


def _exchange(spare, want):
    """Fabric settlement of the enclosures' residuals: local netting first,
    then a proportional fill of net want from net spare. Returns (units
    received per enclosure, units lent per enclosure)."""
    spare_net = np.maximum(spare - want, 0.0)
    want_net = np.maximum(want - spare, 0.0)
    ts, td = spare_net.sum(), want_net.sum()
    scale = min(1.0, ts / max(td, EPS)) if td > 0 else 0.0
    draw = want_net * scale
    frac = spare_net / max(ts, EPS) if ts > 0 else np.zeros_like(spare_net)
    return draw, frac * draw.sum()


def _unloaded_latency(wv, read, miss, remote_frac, offsite_frac, far_frac,
                      offsite_far, plat):
    io = wv["rb"] if read else wv["wb"]
    slices = np.maximum(io / SLICE_BYTES, 1.0)
    proc = (C_PARSE + slices * (C_READ_SLICE if read else C_WRITE_SLICE)) \
        / CLOCK_HZ
    hop, deq = plat["cxl_hop_s"], plat["inter_ssd_op_s"]
    hits = wv["locality"] * (1.0 - miss)
    dram = DRAM_LOOKUP_S * slices + hits * offsite_frac * (deq + hop) \
        + hits * offsite_far * plat["fabric_extra_hops"] * hop
    flash = (T_READ_AVG if read else 8e-6) + io / CHANNEL_BPS \
        + miss * wv["locality"] * T_READ_LSB
    inter = remote_frac * (2 * deq + hop) \
        + far_frac * plat["fabric_extra_hops"] * hop
    link = io / CXL_BPS_PER_SSD + T_HOST_SSD_CMD
    host = T_HOST_STACK + plat["host_extra_clocks"] / HOST_CLOCK_HZ
    return host + link + proc + dram + flash + inter


def simulate(plat, rows, arrivals, n_enclosures, warmup, window_s=1e-3,
             cast=float64):
    """Run the reference over ``arrivals`` [T, n, 2] (read, write bytes per
    window). Returns per-SSD statistics over the measured windows and the
    per-window fleet sums of borrowed and spare DRAM segments."""
    F = cast
    t_n, n_all, _ = arrivals.shape
    e = n_enclosures
    n = n_all // e
    warmup = min(warmup, max(t_n - 1, 0))
    wv = {k: F(v.reshape(e, n)) for k, v in workload_vectors(rows).items()}
    want_frac = F(want_fraction(workload_vectors(rows)).reshape(e, n))
    arr = F(arrivals.reshape(t_n, e, n, 2))
    own_seg = float(max(int(SEGMENTS_FULL * plat["dram_frac"]), 1))
    proc_cap = plat["cores"] * window_s
    hop, deq = plat["cxl_hop_s"], plat["inter_ssd_op_s"]
    xh = plat["fabric_extra_hops"]
    pols = [
        dict(rtype=PROCESSOR, slot0=0, slots=plat["n_slots"],
             claim_rounds=plat["claim_rounds"], lender_cap=plat["claim_rounds"],
             watermark=plat["watermark"], gate_watermark=plat["data_watermark"],
             min_amount=0.0),
        dict(rtype=DRAM, slot0=plat["n_slots"], slots=plat["dram_slots"],
             claim_rounds=plat["claim_rounds"], lender_cap=plat["claim_rounds"],
             watermark=plat["watermark"], gate_watermark=plat["link_watermark"],
             min_amount=1.0),
    ]
    tbl = _Table(e, n, plat["n_slots"] + plat["dram_slots"], F)
    z = lambda: F(np.zeros((e, n)))
    q_r, q_w, bseg, bfar = z(), z(), z(), z()
    prev_proc, prev_flash, prev_link = z(), z(), z()
    acc = {k: z() for k in ("served_r", "served_w", "proc_busy", "flash_busy",
                            "flash_written", "lat_sum", "cmd_count",
                            "cxl_bytes")}
    fab = {k: F(np.zeros(e)) for k in ("proc_in", "proc_out", "seg_in",
                                       "seg_out")}
    ring_b, ring_s = [], []
    cap_bytes = (PEAK_READ_BPS + PEAK_WRITE_BPS) * window_s * 3.0
    amp = F(np.where(wv["wb"] < PAGE_BYTES, SLC_AMP_SMALL_WRITE, 1.0))
    for i in range(t_n):
        q_r = np.minimum(q_r + arr[i, :, :, 0], cap_bytes)
        q_w = np.minimum(q_w + arr[i, :, :, 1], cap_bytes)
        cmds = q_r / wv["rb"] + q_w / wv["wb"]
        slices_r, slices_w = q_r / SLICE_BYTES, q_w / SLICE_BYTES
        seg_eff = own_seg + bseg + bfar
        miss = miss_ratio(wv, np.clip(seg_eff / float(SEGMENTS_FULL), 0, 1))
        off_far = np.where(seg_eff > 0, bfar / np.maximum(seg_eff, 1.0), 0.0)
        off = np.where(seg_eff > 0, bseg / np.maximum(seg_eff, 1.0), 0.0) \
            + off_far
        lookups = cmds * wv["locality"]
        miss_l = lookups * miss
        hit_l = lookups - miss_l
        # §4.5 segment need and spare from the static MRC want
        active = lookups > 1.0
        want_seg = np.where(active, want_frac * SEGMENTS_FULL,
                            DRAM_MIN_KEEP_SEGMENTS)
        seg_need = np.where(active, np.maximum(want_seg - own_seg, 0.0), 0.0)
        seg_spare_gross = np.maximum(
            own_seg - np.maximum(want_seg, DRAM_MIN_KEEP_SEGMENTS), 0.0)
        seg_spare = np.maximum(
            seg_spare_gross - _pool_share(seg_spare_gross, fab["seg_out"]),
            0.0)
        dram_util = np.where(seg_need > 0,
                             1.0 + seg_need / float(SEGMENTS_FULL), 0.0)
        # demands
        ppc = (q_r / wv["rb"] * C_PARSE + slices_r * C_READ_SLICE
               + q_w / wv["wb"] * C_PARSE + slices_w * C_WRITE_SLICE
               + miss_l * C_MISS_EXTRA)
        proc_op_s = ppc / CLOCK_HZ / np.maximum(cmds, EPS)
        log_ops = slices_w * off
        remote_hits = hit_l * off
        remote_far = hit_l * off_far
        proc_dem = (ppc / CLOCK_HZ + log_ops * T_LOG_COMMIT
                    + remote_hits * (deq + hop) + remote_far * xh * hop)
        log_flush = log_ops / 512.0 * (SEGMENT_BYTES / PAGE_BYTES)
        flash_time = ((q_r / PAGE_BYTES + miss_l) / F_READ_PAGES
                      + (q_w / PAGE_BYTES * amp + log_flush) / F_PROG_PAGES)
        host_clocks = cmds * (C_HOST_DRIVER + plat["host_extra_clocks"])
        lookup_b = plat["remote_lookup_bytes"]
        link_time = (q_r + q_w + remote_hits * lookup_b
                     + remote_far * xh * lookup_b) / CXL_BPS_PER_SSD
        # management round every mgmt_interval windows
        if i % plat["mgmt_interval"] == 0:
            tbl = _round(tbl, pols, {
                PROCESSOR: (prev_proc, prev_flash, None),
                DRAM: (dram_util, prev_link, seg_spare)})
        # §4.4 processor harvesting inside the enclosure, then the fabric
        ovh = np.clip((2 * deq + hop) / np.maximum(proc_op_s, 1e-12), 0.0,
                      MAX_OVH)
        surplus = np.maximum(proc_cap - proc_dem, 0.0)
        deficit = np.maximum(proc_dem - proc_cap, 0.0)
        assist_in, used_from = _fluid(
            F(_assist(tbl, PROCESSOR, plat["n_slots"])), surplus, deficit,
            ovh)
        lent = used_from.sum(axis=2)
        remote_frac = np.where(proc_dem > 0,
                               assist_in / np.maximum(proc_dem, EPS), 0.0)
        link_time = link_time + assist_in / np.maximum(proc_op_s, EPS) \
            * CMD_BYTES / CXL_BPS_PER_SSD
        ovh_far = np.clip((2 * deq + hop + xh * hop)
                          / np.maximum(proc_op_s, EPS), 0.0, MAX_OVH)
        out_rem = np.where(prev_proc <= plat["watermark"],
                           np.maximum(surplus - lent, 0.0), 0.0)
        far_out = _pool_share(out_rem, fab["proc_out"])
        resid_def = np.maximum(deficit - assist_in, 0.0)
        far_in = _pool_share(resid_def * (1.0 + ovh_far), fab["proc_in"]) \
            / (1.0 + ovh_far)
        far_frac = np.where(proc_dem > 0,
                            far_in / np.maximum(proc_dem, EPS), 0.0)
        remote_frac = remote_frac + far_frac
        link_time = link_time + far_in / np.maximum(proc_op_s, EPS) \
            * (CMD_BYTES * (1.0 + xh)) / CXL_BPS_PER_SSD
        # §4.5 DRAM segments: claimed locally, the rest across the fabric
        bseg, seg_lent = _fluid(F(_assist(tbl, DRAM, plat["dram_slots"])),
                                seg_spare, seg_need, 0.0)
        resid_need = np.maximum(seg_need - bseg, 0.0)
        bfar = _pool_share(resid_need, fab["seg_in"])
        # joint service
        proc_eff = proc_cap + assist_in - lent + far_in - far_out
        s_proc = proc_eff / np.maximum(proc_dem, EPS)
        s_flash = window_s / np.maximum(flash_time, EPS)
        s_link = window_s / np.maximum(link_time, EPS)
        host_dem = host_clocks.sum(axis=1, keepdims=True) / HOST_CLOCKS_PER_S
        s_host = np.where(host_dem > 0, window_s / np.maximum(host_dem, EPS),
                          np.inf)
        scale = np.clip(np.minimum(np.minimum(s_proc, s_flash),
                                   np.minimum(s_link, s_host)), 0.0, 1.0)
        served_r, served_w = q_r * scale, q_w * scale
        q_r, q_w = q_r - served_r, q_w - served_w
        own_done, remote_done, out_done = _busy_split(
            proc_dem * scale, proc_cap, assist_in, used_from)
        f_own = np.clip(flash_time * scale, 0.0, window_s)
        l_own = np.clip(link_time * scale, 0.0, window_s)
        srv_cmds = served_r / wv["rb"] + served_w / wv["wb"]
        rate = np.maximum(srv_cmds / window_s, EPS)
        lat_r = np.maximum(_unloaded_latency(
            wv, True, miss, remote_frac, off, far_frac, off_far, plat),
            wv["qd"] / rate)
        lat_w = np.maximum(_unloaded_latency(
            wv, False, miss, remote_frac, off, far_frac, off_far, plat),
            wv["qd"] / rate)
        lat = np.where(srv_cmds > 0,
                       (served_r / wv["rb"] * lat_r
                        + served_w / wv["wb"] * lat_w)
                       / np.maximum(srv_cmds, EPS), 0.0)
        cxl = (remote_done / np.maximum(proc_op_s, EPS) * CMD_BYTES
               + log_ops * scale * 64.0 + remote_hits * scale * lookup_b
               + scale * (far_in / np.maximum(proc_op_s, EPS)
                          * CMD_BYTES * (1.0 + xh)
                          + remote_far * xh * lookup_b))
        if i >= warmup:
            acc["served_r"] = acc["served_r"] + served_r
            acc["served_w"] = acc["served_w"] + served_w
            acc["proc_busy"] = acc["proc_busy"] + own_done + out_done
            acc["flash_busy"] = acc["flash_busy"] + f_own
            acc["flash_written"] = acc["flash_written"] + served_w * amp \
                + log_flush * scale * PAGE_BYTES
            acc["lat_sum"] = acc["lat_sum"] + lat * srv_cmds
            acc["cmd_count"] = acc["cmd_count"] + srv_cmds
            acc["cxl_bytes"] = acc["cxl_bytes"] + cxl
        prev_proc = np.where(proc_cap > 0, own_done / max(proc_cap, EPS), 0.0)
        prev_flash = f_own / window_s
        prev_link = l_own / window_s
        ring_b.append(float(np.sum(np.asarray(bseg, np.float64))))
        ring_s.append(float(np.sum(np.asarray(seg_spare, np.float64))))
        # fabric level: enclosures publish residuals; grants apply from the
        # next window and hold for one management interval
        if i % plat["mgmt_interval"] == 0:
            p_sp = np.asarray(out_rem, np.float64).sum(axis=1)
            p_wt = np.asarray(resid_def, np.float64).sum(axis=1)
            s_sp = np.maximum(np.asarray(seg_spare_gross - seg_lent.sum(axis=2),
                                         np.float64), 0.0).sum(axis=1)
            s_wt = np.asarray(resid_need, np.float64).sum(axis=1)
            pin, pout = _exchange(p_sp, p_wt)
            sin, sout = _exchange(s_sp, s_wt)
            fab = {"proc_in": F(pin), "proc_out": F(pout), "seg_in": F(sin),
                   "seg_out": F(sout)}
    t_total = (t_n - warmup) * window_s
    f64 = lambda x: np.asarray(x, np.float64).reshape(n_all)
    acc = {k: f64(v) for k, v in acc.items()}
    return {
        "throughput_bps": (acc["served_r"] + acc["served_w"]) / t_total,
        "latency_s": acc["lat_sum"] / np.maximum(acc["cmd_count"], 1.0),
        "proc_util": acc["proc_busy"] / (plat["cores"] * t_total),
        "flash_util": acc["flash_busy"] / t_total,
        "dwpd": acc["flash_written"] / t_total * 86400.0
        / (SSD_CAPACITY_TB * 1e12),
        "cxl_bytes": acc["cxl_bytes"],
        "borrowed_seg": f64(bseg),
        "borrowed_far": f64(bfar),
        "ring_borrowed": np.array(ring_b),
        "ring_spare": np.array(ring_s),
    }
