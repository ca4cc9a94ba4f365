"""Driver for the JBOF fleet simulator (`repro.jbof.sim.simulate`).

The window is a loop of whole `simulate` calls over the configuration's
fleet. Every call draws its own workload from (seed, call index): the trace
of each enclosure's busy SSDs and the burst arrivals. `sim_ssd_windows_s`
is the SSD-windows simulated (SSDs times windows, warm-up windows included)
over the host seconds of the calls; a call ends when its results are on the
host. Set-up makes one call on the same shapes, which compiles.

`check` runs `reference/sim.py` on every call's workload and compares:
- `fleet_gap`: the fleet's total throughput, flash-time and processor-time
  (utilizations summed over SSDs), as the largest relative gap over the
  three and the calls;
- `idle_ssd_gap`: throughput and flash utilization of every SSD that runs
  the idle workload, as the largest relative gap of one SSD;
- `fleet_latency_gap`: the fleet's mean command latency, each SSD's mean
  latency weighted by its throughput in the reference (the same weights on
  both sides), as the largest relative gap over the calls;
- `segment_overdrafts`: windows in which the fleet's borrowed DRAM
  segments exceeded its published spare segments (conservation), summed
  over the calls, plus SSDs with non-finite statistics.

Latency is compared for the fleet only. Both sides count a window in an
SSD's mean latency wherever it served any command, and a window that serves
the rounding residue of the last one (1e-13 commands) counts a whole window
of queue-depth-bound latency: some idle SSDs' means move by a few percent
with rounding, and the idle SSDs' mean with them. Weighted by throughput
over the fleet, such windows weigh almost nothing.

Busy SSDs are not compared one by one. When two of them are saturated,
their utilizations are equal in exact arithmetic and differ in the last
bit in float32 (and in float64), so the claim sweep's busiest-first order
between them, and so which lenders each one gets, follows rounding; a
sound run swaps their lenders and their own statistics move by up to a few
percent. The fleet's totals and the idle SSDs' own service do not depend
on that order.
"""
from __future__ import annotations

import time

import numpy as np

import traffic_gen
from harness import annotate
from reference import sim as ref

PLATFORM_KEYS = ("cores", "dram_frac", "n_slots", "dram_slots",
                 "claim_rounds", "watermark", "data_watermark",
                 "link_watermark", "mgmt_interval", "fabric_extra_hops",
                 "host_extra_clocks", "inter_ssd_op_s", "cxl_hop_s",
                 "remote_lookup_bytes")
STATS = ("throughput_bps", "proc_util", "flash_util", "cxl_bytes",
         "borrowed_seg", "borrowed_far")
FLEET_STATS = ("throughput_bps", "flash_util", "proc_util")
IDLE_STATS = ("throughput_bps", "flash_util")
WARM_CALL = 2**31 - 1      # the set-up call's workload index
SMALL_SIM = dict(n_enclosures=4, n_windows=60, warmup=20)


def small(cell: dict) -> dict:
    """The loaded ``cell`` shrunk so that a CPU test run holds it."""
    cell["config_file"].update(SMALL_SIM)
    return cell


class Driver:
    def __init__(self, cell, seed, devices, tracer):
        import jax
        from repro.jbof import platforms, sim, workloads

        self.jax, self.sim, self.W = jax, sim, workloads
        self.seed, self.devices, self.tracer = seed, devices, tracer
        conf = cell["config_file"]
        self.conf = conf
        self.limits = conf["limits"]
        plat = platforms.ALL[conf["platform"]]()
        stated = conf["platform_params"]
        for k in PLATFORM_KEYS:
            if abs(float(getattr(plat, k)) - float(stated[k])) > 1e-12 * max(
                    1.0, abs(float(stated[k]))):
                raise ValueError(
                    f"platform {conf['platform']} runs {k}={getattr(plat, k)}"
                    f" but the configuration states {stated[k]}")
        if plat.harvest_flash or plat.harvest_link or plat.vh or plat.oc \
                or plat.flat_sync or not (plat.harvest_proc
                                          and plat.harvest_dram):
            raise ValueError("the plain reference covers processor and DRAM "
                             "harvesting only")
        self.plat, self.params = plat, stated
        self.e = conf["n_enclosures"]
        self.per = conf["ssds_per_enclosure"]
        self.t = conf["n_windows"]
        self.window_s = conf["window_s"]
        self.warmup = conf["warmup"]
        self.scfg = sim.SimConfig(
            window_s=self.window_s, warmup=self.warmup,
            n_enclosures=self.e,
            fabric_federation=conf["fabric_federation"])
        self.traffic = cell["traffic_file"]
        self.calls = []          # (rows, arrivals, result on host)
        self.attempted = self.failed = 0
        self.info = {}

    def _workload(self, call):
        gen = traffic_gen.generator(self.traffic["generator"])
        with annotate("bench_traffic"):
            return gen(self.traffic, self.e, self.per, self.t, self.window_s,
                       self.seed, call)

    def _call(self, rows, arr):
        jax = self.jax
        wl = [self.W.Workload(**r) for r in rows]
        with annotate("bench_simulate"):
            res = self.sim.simulate(self.plat, wl, jax.numpy.asarray(arr),
                                    self.scfg)
        with annotate("bench_readback"):
            out = {k: np.asarray(jax.device_get(getattr(res, k)))
                   for k in STATS + ("latency_s",)}
            out["ring_borrowed"] = np.asarray(
                jax.device_get(res.rings["borrowed_seg"]))
            out["ring_spare"] = np.asarray(
                jax.device_get(res.rings["spare_seg"]))
        return out

    def setup(self):
        rows, arr = self._workload(WARM_CALL)
        self._call(rows, arr)

    def run_window(self, seconds):
        tr = self.tracer
        busy_s, c = 0.0, 0
        t0 = time.perf_counter()
        self.trace_calls = []
        while True:
            rows, arr = self._workload(c)
            first = c == 0
            if first:
                tr.start()
            t1 = time.perf_counter()
            out = self._call(rows, arr)
            dt = time.perf_counter() - t1
            if first:
                tr.stop()
                self.trace_calls = [(t1, t1 + dt)]
            busy_s += dt
            self.calls.append((rows, arr, out))
            c += 1
            if time.perf_counter() - t0 >= seconds:
                break
        n = self.e * self.per
        self.attempted = c
        self.info.update(calls=c, call_s=busy_s / c,
                         window_wall_s=time.perf_counter() - t0)
        return {"sim_ssd_windows_s": n * self.t * c / busy_s}

    def check(self):
        self.jax.clear_caches()
        fleet, idle_gap, over, bad = 0.0, 0.0, 0, 0
        lat_fleet = 0.0
        for rows, arr, out in self.calls:
            want = ref.simulate(self.params, rows, arr, self.e, self.warmup,
                                self.window_s)
            if not all(np.isfinite(out[k]).all()
                       for k in STATS + ("latency_s",)):
                bad += 1
            idle = np.array([r is self.traffic["idle"] for r in rows])
            for k in FLEET_STATS:
                g = np.asarray(out[k], np.float64)
                w = np.asarray(want[k], np.float64)
                fleet = max(fleet, abs(g.sum() - w.sum())
                            / max(abs(w.sum()), 1e-30))
                if k in IDLE_STATS and idle.any():
                    den = np.maximum(np.abs(w[idle]), 1e-30)
                    idle_gap = max(idle_gap, float(np.max(
                        np.abs(g[idle] - w[idle]) / den)))
            g = np.asarray(out["latency_s"], np.float64)
            w = np.asarray(want["latency_s"], np.float64)
            tp = np.asarray(want["throughput_bps"], np.float64)
            lat_fleet = max(lat_fleet, _mean_gap(g, w, tp))
            over += int(np.sum(out["ring_borrowed"].sum(axis=1)
                               > out["ring_spare"].sum(axis=1) + 1e-3))
        self.failed = bad
        self.info.update(calls_checked=len(self.calls))
        lim = self.limits
        checks = {"fleet_gap": float(fleet), "idle_ssd_gap": float(idle_gap),
                  "fleet_latency_gap": lat_fleet,
                  "segment_overdrafts": float(over + bad)}
        return {k: {"value": v, "limit": lim[k], "ok": bool(v <= lim[k])}
                for k, v in checks.items()}

    def layer_context(self, red, pk, clog):
        (t1, t2), = self.trace_calls
        comp = clog.between(t1, t2)
        return {"reduced": red, "peaks": pk,
                "windows": self.t, "calls": 1,
                "compile_s": comp["compile_s"]}


def _mean_gap(got, want, weight) -> float:
    """Relative gap of the ``weight``-weighted means of two arrays."""
    mean = float(np.sum(want * weight))
    return abs(float(np.sum(got * weight)) - mean) / max(abs(mean), 1e-30)
