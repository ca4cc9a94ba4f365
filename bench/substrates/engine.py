"""Driver for the harvesting serving engine (`repro.serving.engine`).

The window is a loop of one engine step per iteration: the step's arrivals
come from the traffic mix, the step is dispatched (`engine.step` on one
chip, `engine.make_sharded_step` on four), and the step's active and queued
counts are read back to the host, as a server streaming tokens must. Tokens
decoded in step t are the requests in the system before it, plus its
arrivals, less those still queued after it: N(t-1) + a(t) - Q(t).

`check` compares what the timed path produced with `reference/engine.py`:
- `kv_row_gap`: every K and V row of every live sequence in the pool the
  window left, against the reference's rows, as the largest relative L2 gap
  of a row;
- `attn_norm_gap`: the paged-attention output of the same compiled step.
  After the window the traffic stops and the engine drains; its first step
  attends over every sequence the window left, the later ones over fewer.
  Each step's attention statistic (the sum of its squared outputs, the one
  reading of the kernel's output the engine gives) against the reference's
  attention over the same sequences, as the largest relative gap of a step;
- `held_tokens_gap`: the tokens the live sequences hold in that pool,
  against what the requests' lives say they must hold (a request admitted
  j steps before the window's last step holds j + 1 tokens; admissions
  follow from the counts read back each step);
- `lost_tokens`: once drained, every request that arrived, from the first
  step on, must have decoded exactly its 16 tokens;
- `link_overdrafts`: on a seed-drawn sample of the window's steps, how
  often a replica's redirect and spill bytes exceeded its LINK_BW budget,
  or the fleet's budgets exceeded the allowance it published;
- `twin_mismatches` (cells on several chips): from the state the window
  left, a few more steps of the sharded program and of `engine.step` (the
  same shard-local function under `vmap` on one chip) on the same
  arrivals; integer statistics and state must agree exactly, float ones to
  1e-5. Cross-shard redirection is the only traffic between chips, so a
  collective that drops or garbles it shows here.
"""
from __future__ import annotations

import time

import numpy as np

import traffic_gen
from harness import annotate
from reference import engine as ref

REQUEST_TOKENS = 16
WARM_STEPS = 48
TRACE_STEPS = 200
TWIN_STEPS = 16
TWIN_TOL = 1e-5
DRAIN_MAX = 400
SAMPLE_P = 1.0 / 16
SMALL_ENGINE = dict(n_replicas=8, n_shards=2, pages_per_replica=16)
SMALL_WIDTHS = dict(hidden_size=128, num_attention_heads=4,
                    num_key_value_heads=2)
# a quarter of the replicas: a quarter of the cells' 10 requests a step
SMALL_REQUESTS_PER_STEP = 2.5


def small(cell: dict) -> dict:
    """The loaded ``cell`` shrunk so that a CPU test run holds it."""
    conf = cell["config_file"]
    conf["engine"].update(SMALL_ENGINE)
    conf.update(SMALL_WIDTHS)
    cell["traffic_file"]["requests_per_step"] = SMALL_REQUESTS_PER_STEP
    return cell


class Driver:
    def __init__(self, cell, seed, devices, tracer):
        import jax
        from repro.serving import engine as E

        self.E = E
        self.jax = jax
        self.seed, self.devices, self.tracer = seed, devices, tracer
        conf = cell["config_file"]
        self.cfg_file = conf
        self.cfg = E.EngineConfig(**engine_config(conf))
        self.operand = conf["matmul_operands"][devices[0].platform]
        self.limits = conf["limits"]
        tr = cell["traffic_file"]
        self.traffic = traffic_gen.generator(tr["generator"])(
            tr, self.cfg.n_replicas, seed)
        self.sample = traffic_gen.rng(seed, 5)
        self.attempted = self.failed = 0
        self.info = {}
        self.i = 0                       # next step index
        self.arrived = 0                 # requests offered so far
        self.decoded = 0                 # tokens decoded so far
        self.n_prev = 0                  # requests in the system (Q + A)
        self.q_prev = 0
        self.admitted = []               # per step, for live lengths
        self.link_samples = []

    # ------------------------------------------------------------ set-up
    def _weights(self):
        jax = self.jax
        c = self.cfg
        d = c.n_heads * c.head_dim
        ks = traffic_gen.key_seed(self.seed, 0)
        fn = jax.jit(ref.weights, static_argnums=(1, 2, 3, 4))
        return fn(ks, d, c.n_heads, c.kv_heads, c.head_dim), ks

    def setup(self):
        jax, E = self.jax, self.E
        c = self.cfg
        state = E.init(c, jax.random.key(traffic_gen.key_seed(self.seed, 4)))
        (wq, wk, wv, wo), self.key_seed = self._weights()
        state = state._replace(wq=wq, wk=wk, wv=wv, wo=wo)
        if len(self.devices) > 1:
            from repro.launch.mesh import make_serving_mesh
            from repro.launch.sharding import engine_state_shardings

            mesh = make_serving_mesh(c.n_shards)
            state = jax.device_put(state, engine_state_shardings(c, mesh))
            self._step = E.make_sharded_step(c, mesh)
        else:
            self._step = lambda s, a: E.step(c, s, a)
        self.state = state
        for _ in range(WARM_STEPS):
            self._one()

    def _one(self, keep_link=False, arrivals=None):
        """One step of the serving loop; returns (tokens decoded, stats)."""
        with annotate("bench_traffic"):
            a = self.traffic.step(self.i) if arrivals is None else arrivals
        with annotate("bench_dispatch"):
            self.state, st = self._step(self.state, a)
        with annotate("bench_readback"):
            act, q = self.jax.device_get((st["active"], st["queued"]))
        act, q = int(act), int(q)
        a_t = int(a.sum())
        dec = self.n_prev + a_t - q
        # admissions of this step: active now, less active before, plus
        # the sequences that finished in it
        done = self.n_prev + a_t - (q + act)
        self.admitted.append(act - (self.n_prev - self.q_prev) + done)
        self.n_prev, self.q_prev = q + act, q
        self.arrived += a_t
        self.decoded += dec
        self.i += 1
        if keep_link:
            self.link_samples.append((st["link_budget_bytes"],
                                      st["link_redirect_bytes"],
                                      st["link_spill_bytes"]))
        return dec, st

    # ------------------------------------------------------------ window
    def run_window(self, seconds):
        tr = self.tracer
        i0, dec0, arr0 = self.i, self.decoded, self.arrived
        t0 = time.perf_counter()
        tr.start()
        traced = True
        self.trace_i0 = self.i
        while True:
            keep = self.sample.random() < SAMPLE_P
            self._one(keep_link=keep)
            if traced and self.i - self.trace_i0 >= TRACE_STEPS:
                tr.stop()
                self.trace_i1 = self.i
                traced = False
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        if traced:
            tr.stop()
            self.trace_i1 = self.i
        steps = self.i - i0
        self.attempted = self.arrived - arr0
        self.info.update(window_steps=steps,
                         step_ms=(t1 - t0) / steps * 1e3,
                         window_requests=self.attempted)
        return {"decode_tok_s": (self.decoded - dec0) / (t1 - t0)}

    # ------------------------------------------------------------- check
    def _snapshot(self, table=True):
        p = self.state.pool
        fields = [("active", p.seq_active), ("len", p.seq_len)]
        if table:
            fields.append(("table", p.page_table))
        return {k: np.asarray(self.jax.device_get(v)).reshape(
            -1, *v.shape[2:]) for k, v in fields}

    def _pool_kv(self):
        """K and V pools as float values (int8 codes times page scales)."""
        p = self.state.pool
        k, v, ks, vs = (np.asarray(self.jax.device_get(x)) for x in
                        (p.k, p.v, p.k_scale, p.v_scale))
        if k.dtype == np.int8:
            k = k.astype(np.float32) * ks[..., None, None, None]
            v = v.astype(np.float32) * vs[..., None, None, None]
        return k, v

    def _rows(self, snap, k, v, last_step):
        """[(step, row, k_row, v_row)] of the live sequences from the pool,
        located through the page table."""
        c = self.cfg
        st = c.seq_slots + c.shadow_slots
        nl = c.n_replicas // c.n_shards
        pages_shard = nl * c.pages_per_replica
        kf = k.reshape(-1, c.page, c.kv_heads, c.head_dim)
        vf = v.reshape(-1, c.page, c.kv_heads, c.head_dim)
        table = snap["table"].reshape(-1, c.max_pages)
        lens = snap["len"].reshape(-1)
        act = snap["active"].reshape(-1)
        rows = {r: int(lens[r]) for r in np.nonzero(act & (lens > 0))[0]}
        out = []
        for r, n in rows.items():
            shard = (r // st) // nl
            for j in range(n):
                phys = int(table[r, j // c.page])
                g = shard * pages_shard + phys
                out.append((last_step - n + 1 + j, r,
                            kf[g, j % c.page], vf[g, j % c.page]))
        return out

    def _drain(self):
        """Steps with no arrivals until every request has finished; returns
        [(step, [(row, length)] attended, attention statistic)]."""
        zero = np.zeros(self.cfg.n_replicas, np.int32)
        prev = self._snapshot(table=False)
        drains = []
        for _ in range(DRAIN_MAX):
            if self.n_prev == 0:
                break
            _, st = self._one(arrivals=zero)
            cur = self._snapshot(table=False)
            # attended: the slots live after the step, and those live before
            # it that finished in it (released after their last token)
            live = cur["active"] & (cur["len"] > 0)
            gone = prev["active"] & ~cur["active"]
            att = [(int(r), int(cur["len"][r])) for r in np.nonzero(live)[0]]
            att += [(int(r), int(prev["len"][r]) + 1)
                    for r in np.nonzero(gone)[0]]
            drains.append((self.i - 1, att,
                           float(self.jax.device_get(st["attn_norm"]))))
            prev = cur
        return drains

    def check(self):
        jax = self.jax
        c = self.cfg
        # the benchmark's own draw of the weights, again, on the host
        w = [np.asarray(a) for a in jax.device_get(self._weights()[0])]
        twin = self._twin() if len(self.devices) > 1 else None
        # the pool the window left
        snap = self._snapshot()
        k, v = self._pool_kv()
        window_rows = self._rows(snap, k, v, self.i - 1)
        held = int(snap["len"][snap["active"]].sum())
        adm = self.admitted
        due = sum((j + 1) * adm[self.i - 1 - j]
                  for j in range(REQUEST_TOKENS - 1) if self.i - 1 - j >= 0)
        drains = self._drain()
        lost = abs(REQUEST_TOKENS * self.arrived - self.decoded)
        self.failed = min(self.attempted, self.n_prev)
        links = [tuple(np.asarray(jax.device_get(x), np.float64) for x in s)
                 for s in self.link_samples]
        del self.state, self.link_samples
        jax.clear_caches()

        # the reference, on the host, after the program's state is freed
        kv_gap, attn_gap = self._compare(w, window_rows, drains)
        int8 = c.kv_quant == "int8"
        page_b = 2 * c.page * c.kv_heads * c.head_dim * (1 if int8 else 4) \
            + (8 if int8 else 0)
        allowance = c.link_pages_per_step * page_b * c.n_replicas
        over = 0
        for b, rd, sp in links:
            over += int(np.sum(rd + sp > b + 1e-5))
            over += int(b.sum() > allowance * (1 + 1e-6))
        self.info.update(live_rows_checked=len(window_rows),
                         drain_steps_checked=len(drains),
                         first_drain_step_sequences=len(drains[0][1])
                         if drains else 0,
                         link_steps_sampled=len(links),
                         requests_total=self.arrived)
        lim = self.limits
        checks = {
            "kv_row_gap": float(kv_gap), "attn_norm_gap": float(attn_gap),
            "held_tokens_gap": float(abs(held - due)),
            "lost_tokens": float(lost), "link_overdrafts": float(over)}
        if twin is not None:
            checks["twin_mismatches"] = float(twin)
        return {k: {"value": v, "limit": lim[k], "ok": bool(v <= lim[k])}
                for k, v in checks.items()}

    def reference_layer(self, w, cast=ref.float64, operand=None):
        c = self.cfg
        return ref.Layer(w, c.n_heads, c.kv_heads, c.head_dim,
                         c.n_replicas // c.n_shards,
                         c.seq_slots + c.shadow_slots,
                         operand=self.operand if operand is None else operand,
                         cast=cast, device=self.devices[0])

    def _compare(self, w, window_rows, drains):
        """(kv_row_gap, attn_norm_gap) of the program's outputs."""
        layer = self.reference_layer(w)
        kv_gap = 0.0
        for step, r, kr, vr in window_rows:
            for got, want in zip((kr, vr), layer.kv_row(step, r)):
                g = np.linalg.norm(np.asarray(got, np.float64) - want)
                kv_gap = max(kv_gap, g / max(np.linalg.norm(want), 1e-30))
        attn_gap = 0.0
        for step, att, got in drains:
            want = layer.attn_norm(step, att)
            if att:
                attn_gap = max(attn_gap, abs(got - want) / max(want, 1e-30))
        return kv_gap, attn_gap

    def _twin(self):
        """Steps of the sharded program beside `engine.step` on one chip,
        from the same state and arrivals; returns the mismatches."""
        jax, E, c = self.jax, self.E, self.cfg
        one_chip = jax.device_put(jax.device_get(self.state), self.devices[0])
        bad, cross = 0, 0
        for _ in range(TWIN_STEPS):
            a = self.traffic.step(self.i)
            _, st = self._one(arrivals=a)
            one_chip, ts = E.step(c, one_chip, a)
            got, want = jax.device_get((st, ts))
            bad += _mismatches(got, want)
            cross += int(got["cross_redirected"])
        got, want = jax.device_get((self.state, one_chip))
        bad += _mismatches(got, want)
        self.info.update(twin_steps=TWIN_STEPS, twin_cross_redirected=cross)
        return bad

    # ------------------------------------------------------- layer metrics
    def layer_context(self, red, pk, clog):
        """What the per-layer readers read: the reduced trace, the peaks,
        and the traced steps' counts and live lengths."""
        c = self.cfg
        adm = self.admitted
        lengths = []
        for t in range(self.trace_i0, self.trace_i1):
            # live cache lengths after step t's append: a request admitted
            # at step s holds t - s + 1 tokens for REQUEST_TOKENS steps
            ls = []
            for j in range(REQUEST_TOKENS):
                if t - j >= 0:
                    ls += [j + 1] * max(adm[t - j], 0)
            lengths.append(ls)
        return {
            "reduced": red, "peaks": pk, "cfg": c,
            "steps": self.trace_i1 - self.trace_i0,
            "span_s": red.window_ps / 1e12,
            "live_lengths": lengths,
            "d_model": c.n_heads * c.head_dim,
            "kv_bytes": 1 if c.kv_quant == "int8" else 4,
        }


def engine_config(conf: dict) -> dict:
    """`EngineConfig` keywords: the configuration's `engine` block, with the
    attention widths taken from the model's own keys."""
    h = conf["num_attention_heads"]
    return dict(conf["engine"], n_heads=h,
                kv_heads=conf["num_key_value_heads"],
                head_dim=conf["hidden_size"] // h)


def _mismatches(got, want) -> int:
    """Leaves of two pytrees that differ: integers and booleans at all,
    floats by more than TWIN_TOL."""
    import jax

    bad = 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            bad += 1
        elif np.issubdtype(a.dtype, np.floating):
            bad += int(not np.allclose(a, b, rtol=TWIN_TOL, atol=TWIN_TOL))
        else:
            bad += int(not np.array_equal(a, b))
    return bad
