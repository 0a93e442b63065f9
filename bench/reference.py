"""Plain reference simulator for the benchmark's `correct` check.

A straightforward event loop over one replica, written from the model's
definition and importing nothing of the program under test:

* requests: RAN requests run DU (GPU) then, after one transport hop when
  the CU-UP sits elsewhere, CU-UP (CPU); AI requests are routed on arrival
  to the replica of their service with the smallest expected wait and
  queue there after the hop from their cell's DU node;
* every instance serves the head of its FIFO, GPU stage first, then CPU,
  at its allocated rates (the event core: next completion, advance);
* after every event the nodes it touched re-solve the deadline-aware
  allocation (urgency-weighted square-root shares over RAN floors, an
  active-set fixed point), and every node re-solves at least every 0.25 s
  of simulated time and after every epoch;
* at each epoch the placement policy sees a snapshot and may migrate one
  instance (the stand-in agent of HAF without a critic: priority scores
  P1-P3 plus a hashed jitter, highest above a threshold wins; P3 reads
  each service's arrival rate over the epoch that closes).

Inputs are plain data: the deployment as lists of numbers and the request
table, both made by the harness from the seed.  ``core_dtype`` is the
number format of the event core's arithmetic; float32 is the control,
the precision below the float64 the configuration states.
"""
from __future__ import annotations

import hashlib
import heapq
import math
from collections import deque

import numpy as np

INF = math.inf
DU, CUUP, LARGE_AI, SMALL_AI = 0, 1, 2, 3
RAN, LARGE, SMALL = 0, 1, 2            # request classes

EPS_URGENCY = 1e-3        # urgency denominator clamp (s)
EPS_FLOOR = 1e-4          # floor denominator clamp (s)
EPS_ALLOC = 1e-9          # share denominator clamp
FLOOR_MARGIN = 0.9        # RAN floors finish 10% before the deadline
REFRESH_S = 0.25          # every node re-solves at least this often
ROUTE_RATE_FLOOR = 1e6    # routing divides backlog by max(rate, this)
CUUP_EMA0 = 5e-4          # initial CU-UP time estimate per cell (s)


def tree_sum(vals):
    """Sum by pairwise halving over the values zero-padded to a power of
    two: the reduction order the allocator is defined with."""
    vals = list(vals)
    k = 1
    while k < len(vals):
        k <<= 1
    vals += [0.0] * (k - len(vals))
    while len(vals) > 1:
        h = len(vals) // 2
        vals = [vals[i] + vals[i + h] for i in range(h)]
    return vals[0] if vals else 0.0


def active_set(w, floors, cap):
    """Shares ``w_i * rem / sum(w)`` over the unpinned entries, entries
    whose share falls below their floor pinned at the floor; floors that
    exceed the capacity are scaled down to it first."""
    n = len(w)
    floor_sum = tree_sum(floors)
    if floor_sum > cap + 1e-6 and floor_sum > 0.0:
        scale = cap / floor_sum
        floors = [f * scale for f in floors]
    pinned = [x <= 0.0 for x in w]

    def sums():
        rem = max(cap - tree_sum([floors[i] if pinned[i] else 0.0
                                  for i in range(n)]), 0.0)
        den = max(tree_sum([0.0 if pinned[i] else w[i]
                            for i in range(n)]), EPS_ALLOC)
        return rem, den

    for _ in range(n):
        rem, den = sums()
        grew = False
        for i in range(n):
            if not pinned[i] and w[i] * rem / den < floors[i]:
                pinned[i] = True
                grew = True
        if not grew:
            break
    rem, den = sums()
    return [floors[i] if pinned[i] else w[i] * rem / den for i in range(n)]


class Replica:
    """One seed of one deployment under one placement method."""

    def __init__(self, dep, requests, horizon, method,
                 epoch_interval=5.0, core_dtype=np.float64):
        self.dep = dep
        self.method = method
        self.dt = np.dtype(core_dtype)
        self.N = len(dep["gpu"])
        self.S = len(dep["cat"])
        self.gpu = np.asarray(dep["gpu"], np.float64)
        self.cpu = np.asarray(dep["cpu"], np.float64)
        self.vram = np.asarray(dep["vram"], np.float64)
        self.cat = list(dep["cat"])
        self.weight = np.asarray(dep["weight"], np.float64)
        self.delta = float(dep["delta"])
        self.placement = np.asarray(dep["placement"], np.int64).copy()
        self.node_sids = [[] for _ in range(self.N)]
        for s in range(self.S):
            self.node_sids[self.placement[s]].append(s)
        self.du_of, self.cuup_of = {}, {}
        for s, (c, cell) in enumerate(zip(self.cat, dep["cell"])):
            if c == DU:
                self.du_of[cell] = s
            elif c == CUUP:
                self.cuup_of[cell] = s
        self.cuup_ema = {cell: CUUP_EMA0 for cell in self.cuup_of}
        self.alpha = np.zeros(self.S)
        for cell, s in self.du_of.items():
            self.alpha[s] = self.cuup_ema.get(cell, CUUP_EMA0)

        S = self.S
        self.queues = [deque() for _ in range(S)]
        self.rem_g = np.zeros(S)
        self.rem_c = np.zeros(S)
        self.head_kv = np.zeros(S)
        self.busy = np.zeros(S, bool)
        self.started = np.zeros(S, bool)
        self.tail_g = np.zeros(S)
        self.tail_c = np.zeros(S)
        self.alloc_g = np.zeros(S)
        self.alloc_c = np.zeros(S)
        self.reconfig_until = np.zeros(S)

        self.req = requests
        self.finish = np.full(len(requests), -1.0)
        self.stage_entered = np.zeros(len(requests))
        self.heap = []
        for k in range(1, int(horizon / epoch_interval) + 3):
            self.heap.append((k * epoch_interval, (0, k), "epoch", k))
        for i, r in enumerate(requests):
            if r[1] == RAN:
                self.heap.append((r[2], (1, i), "du", i))
            else:
                self.heap.append((r[2] + dep["ran_packet"], (1, i),
                                  "ai_route", i))
        heapq.heapify(self.heap)
        self.seq = 0
        self.arrivals = {}
        self.epoch_interval = epoch_interval
        self.t = 0.0
        self.n_events = 0
        self.infeasible = 0
        self.migrations = []
        self.dirty = set()
        self.last_full = 0.0

    # -- queues ----------------------------------------------------------- #
    def push(self, t, kind, payload):
        heapq.heappush(self.heap, (t, (3, self.seq), kind, payload))
        self.seq += 1

    def push_job(self, s, i, rem_g, rem_c, kv=0.0):
        q = self.queues[s]
        q.append([i, rem_g, rem_c, kv])
        if len(q) == 1:
            self._promote(s)
        else:
            self.tail_g[s] += rem_g
            self.tail_c[s] += rem_c
        self.dirty.add(int(self.placement[s]))

    def pop_job(self, s):
        q = self.queues[s]
        i = q.popleft()[0]
        if q:
            self.tail_g[s] -= q[0][1]
            self.tail_c[s] -= q[0][2]
        self._promote(s)
        return i

    def _promote(self, s):
        q = self.queues[s]
        if q:
            _, rg, rc, kv = q[0]
            self.rem_g[s], self.rem_c[s], self.head_kv[s] = rg, rc, kv
            self.busy[s] = True
        else:
            self.rem_g[s] = self.rem_c[s] = self.head_kv[s] = 0.0
            self.busy[s] = False
        self.started[s] = False

    def deadline(self, i):
        return self.req[i][2] + self.req[i][3]

    def hops(self, s_a, s_b):
        return 0 if self.placement[s_a] == self.placement[s_b] else 1

    # -- event core ------------------------------------------------------- #
    def _service_times(self, t):
        d = self.dt
        rg, rc = self.rem_g.astype(d), self.rem_c.astype(d)
        g, c = self.alloc_g.astype(d), self.alloc_c.astype(d)
        avail = self.busy & (self.reconfig_until <= t)
        with np.errstate(divide="ignore", invalid="ignore"):
            dt_g = np.where(rg > 0, rg / g, d.type(0))
            dt_c = np.where(rc > 0, rc / c, d.type(0))
        return rg, rc, g, c, avail, dt_g, dt_c

    def next_completion(self, t):
        *_, avail, dt_g, dt_c = self._service_times(t)
        cand = np.where(avail, (dt_g + dt_c) + self.dt.type(t), INF)
        s = int(np.argmin(cand))
        best = float(cand[s])
        return (best, s) if math.isfinite(best) else (INF, -1)

    def advance(self, t, dt):
        if dt <= 0.0:
            return
        rg, rc, g, c, avail, dt_g, dt_c = self._service_times(t)
        dt = self.dt.type(dt)
        zero = self.dt.type(0)
        run_g = avail & (rg > 0) & (g > 0)
        tg = np.minimum(dt_g, dt)
        rg = rg - np.where(run_g, g * tg, zero)
        rem_dt = dt - tg
        cpu = avail & (rg <= 0) & (rc > 0) & (rem_dt > 0) & (c > 0)
        tc = np.minimum(dt_c, rem_dt)
        rc = rc - np.where(cpu, c * tc, zero)
        self.rem_g[:] = rg
        self.rem_c[:] = rc
        self.started |= run_g | cpu

    # -- allocation ------------------------------------------------------- #
    def servable(self, n, t):
        return [s for s in self.node_sids[n]
                if self.busy[s] and t >= self.reconfig_until[s]]

    def head_inputs(self, s, t, gcap, ccap):
        """(w_g, w_c, floor_g, floor_c) of one servable head; counts a
        RAN floor whose slack is already gone."""
        dls = [self.deadline(job[0]) for job in self.queues[s]]
        omega = tree_sum([1.0 / max(d - t, EPS_URGENCY) for d in dls])
        psi_g = max(float(self.tail_g[s]) + float(self.rem_g[s]), 0.0)
        psi_c = max(float(self.tail_c[s]) + float(self.rem_c[s]), 0.0)
        fg = fc = 0.0
        if self.cat[s] == DU:
            slack = (min(dls) - t - self.delta
                     - float(self.alpha[s])) * FLOOR_MARGIN
            self.infeasible += slack <= 0.0
            fg = min(psi_g / max(slack, EPS_FLOOR), gcap)
        elif self.cat[s] == CUUP:
            slack = (min(dls) - t) * FLOOR_MARGIN
            self.infeasible += slack <= 0.0
            fc = min(psi_c / max(slack, EPS_FLOOR), ccap)
        return psi_g, psi_c, omega, fg, fc

    def allocate(self, t, nodes):
        if nodes is None:
            self.alloc_g[:] = 0.0
            self.alloc_c[:] = 0.0
            nodes = range(self.N)
        else:
            for n in nodes:
                for s in self.node_sids[n]:
                    self.alloc_g[s] = self.alloc_c[s] = 0.0
        for n in nodes:
            sids = self.servable(n, t)
            if not sids:
                continue
            gcap, ccap = float(self.gpu[n]), float(self.cpu[n])
            wg, wc, fg, fc = [], [], [], []
            for s in sids:
                psi_g, psi_c, omega, f_g, f_c = self.head_inputs(
                    s, t, gcap, ccap)
                wg.append(math.sqrt(omega * psi_g))
                wc.append(math.sqrt(omega * psi_c))
                fg.append(f_g)
                fc.append(f_c)
            for s, a, b in zip(sids, active_set(wg, fg, gcap),
                               active_set(wc, fc, ccap)):
                self.alloc_g[s] = a
                self.alloc_c[s] = b

    def realloc(self, t):
        if t - self.last_full >= REFRESH_S or len(self.dirty) >= self.N:
            self.last_full = t
            self.dirty.clear()
            self.allocate(t, None)
        elif self.dirty:
            nodes = sorted(self.dirty)
            self.dirty.clear()
            self.allocate(t, nodes)

    # -- events ----------------------------------------------------------- #
    def complete(self, s, t):
        i = self.pop_job(s)
        r = self.req[i]
        if self.cat[s] == DU:
            cu = self.cuup_of[r[4]]
            self.push(t + self.hops(s, cu) * self.delta, "cuup", i)
            return
        self.finish[i] = t
        if self.cat[s] == CUUP:
            cell = r[4]
            ema = self.cuup_ema.get(cell, t - self.stage_entered[i])
            new = 0.9 * ema + 0.1 * (t - self.stage_entered[i])
            self.cuup_ema[cell] = new
            if cell in self.du_of:
                self.alpha[self.du_of[cell]] = new

    def timed(self, t):
        _, _, kind, x = heapq.heappop(self.heap)
        if kind == "du":
            r = self.req[x]
            self.push_job(self.du_of[r[4]], x, max(r[5], 1.0), max(r[6], 0.0))
            self.arrivals["ran"] = self.arrivals.get("ran", 0) + 1
        elif kind == "cuup":
            self.stage_entered[x] = t
            self.push_job(self.cuup_of[self.req[x][4]], x, 0.0,
                          max(self.req[x][7], 1e-9))
        elif kind == "ai_route":
            r = self.req[x]
            sids = np.asarray(self.dep["service_sids"][r[11]], np.int64)
            wait = ((self.tail_g[sids] + self.rem_g[sids])
                    / np.maximum(self.alloc_g[sids], ROUTE_RATE_FLOOR)
                    + np.maximum(self.reconfig_until[sids] - t, 0.0))
            s = int(sids[int(np.argmin(wait))])
            hop = self.hops(self.du_of[r[4]], s)
            self.push(t + hop * self.delta, "ai_enqueue", (x, s))
            self.arrivals[r[11]] = self.arrivals.get(r[11], 0) + 1
        elif kind == "ai_enqueue":
            i, s = x
            r = self.req[i]
            self.stage_entered[i] = t
            self.push_job(s, i, max(r[8], 1.0), max(r[9], 0.0), r[10])
        elif kind == "epoch":
            self.epoch(x, t)
        elif kind == "mig_done":
            self.dirty.add(int(self.placement[x]))
        else:
            raise ValueError(f"unknown event {kind!r}")

    # -- epochs ----------------------------------------------------------- #
    def snapshot(self, t):
        """The node and instance view the placement layer decides on."""
        N, S = self.N, self.S
        psi_g, psi_c = np.zeros((N, S)), np.zeros((N, S))
        fg, fc = np.zeros((N, S)), np.zeros((N, S))
        for n in range(N):
            for s in self.servable(n, t):
                psi_g[n, s], psi_c[n, s], _, fg[n, s], fc[n, s] = \
                    self.head_inputs(s, t, float(self.gpu[n]),
                                     float(self.cpu[n]))
        g_used, c_used, vram_used = np.zeros(N), np.zeros(N), np.zeros(N)
        np.add.at(g_used, self.placement, self.alloc_g)
        np.add.at(c_used, self.placement, self.alloc_c)
        kv = np.where(self.started, self.head_kv, 0.0)
        np.add.at(vram_used, self.placement, self.weight + kv)
        g_den = np.maximum(self.gpu, 1e-9)
        c_den = np.maximum(self.cpu, 1e-9)
        psi_inst = psi_g.sum(axis=0)
        psi_node = np.zeros(N)
        np.add.at(psi_node, self.placement, psi_inst)
        return {"gpu_util": g_used / g_den, "cpu_util": c_used / c_den,
                "ran_floor_g": fg.sum(axis=1) / g_den,
                "ran_floor_c": fc.sum(axis=1) / c_den,
                "headroom": self.vram - vram_used, "kv": kv,
                "psi_g": psi_inst, "psi_c": psi_c.sum(axis=0),
                "psi_node": psi_node}

    def epoch(self, k, t):
        # the snapshot carries the arrival rate of each service over the
        # epoch that closes here; the window then starts afresh
        snap = self.snapshot(t)
        snap["rates"] = {svc: n / self.epoch_interval
                         for svc, n in self.arrivals.items()}
        self.arrivals.clear()
        action = None
        if self.method["placement"] == "stand-in":
            action = self.stand_in(k, t, snap)
        if action is not None:
            s, src, dst = action
            if (src != dst and self.placement[s] == src
                    and snap["headroom"][dst] >= self.weight[s] + snap["kv"][s]
                    and t >= self.reconfig_until[s]):
                self.placement[s] = dst
                self.node_sids[src].remove(s)
                self.node_sids[dst].append(s)
                until = t + float(self.dep["reconfig_s"][s])
                self.reconfig_until[s] = until
                self.migrations.append((t, s, src, dst))
                self.push(until, "mig_done", s)
        self.dirty.update(range(self.N))

    def stand_in(self, k, t, snap):
        """The stand-in agent's pick: candidate moves of every instance
        not reconfiguring to every other node with the VRAM for it."""
        cand = []
        for s in range(self.S):
            if not self.dep["movable"][s] or t < self.reconfig_until[s]:
                continue
            src = int(self.placement[s])
            need = self.weight[s] + float(snap["kv"][s])
            cand += [(s, src, d) for d in range(self.N)
                     if d != src and snap["headroom"][d] >= need]
        if not cand:
            return None
        p = self.method["agent"]
        sids = np.array([a[0] for a in cand])
        srcs = np.array([a[1] for a in cand])
        dsts = np.array([a[2] for a in cand])
        gf, cc = self.gpu, self.cpu
        psi = snap["psi_g"][sids]
        psi_c = snap["psi_c"][sids]
        node = snap["psi_node"]
        util_g, util_c = snap["gpu_util"], snap["cpu_util"]
        # P2: GPU contention relief, gated by the service's own backlog
        src_load = ((node[srcs] - psi) / np.maximum(gf[srcs], 1.0)
                    + 0.5 * util_g[srcs])
        dst_load = ((node[dsts] - np.where(srcs == dsts, psi, 0.0))
                    / np.maximum(gf[dsts], 1.0) + 0.5 * util_g[dsts])
        slower = psi / gf[dsts] - psi / gf[srcs]
        relief = np.tanh(psi / gf[srcs]) * (src_load - dst_load - slower)
        # P2: the same for CPU-bound instances
        cpu_relief = np.tanh(psi_c / cc[srcs]) * (
            util_c[srcs] - util_c[dsts]
            - (psi_c / cc[dsts] - psi_c / cc[srcs]))
        # P1: RAN floors at the destination, relief at the source for AI
        floors = snap["ran_floor_g"] + snap["ran_floor_c"]
        is_ai = np.array([self.cat[s] >= LARGE_AI for s in sids])
        p1 = p["ran_weight"] * (0.3 * np.where(is_ai, floors[srcs], 0.0)
                                - 1.0 * floors[dsts])
        # P3: the reconfiguration outage, scaled by the service's traffic
        rates = np.array([snap["rates"].get(self.dep["arch"][s], 0.0)
                          for s in sids])
        rcfg = np.array([self.dep["reconfig_s"][s] for s in sids])
        outage = p["outage_weight"] * rcfg * (0.05 + 0.02 * rates)
        score = relief + cpu_relief + p1 - outage + p["eagerness"]
        best, best_a = -INF, None
        for sc, a in zip(score, cand):
            key = f"{p['name']}:{p['seed']}:{k}:mig:s{a[0]}:n{a[1]}->n{a[2]}"
            h = int(hashlib.sha256(key.encode()).hexdigest()[:8], 16)
            sc = float(sc) + (h / 0xFFFFFFFF - 0.5) * 2 * p["noise"]
            if sc > best:
                best, best_a = sc, a
        return best_a if best > p["threshold"] else None

    # -- main loop -------------------------------------------------------- #
    def run(self, max_events=5_000_000):
        self.allocate(0.0, None)
        truncated = False
        while True:
            t_comp, s = self.next_completion(self.t)
            t_ev = self.heap[0][0] if self.heap else INF
            t_next = min(t_comp, t_ev)
            if not math.isfinite(t_next):
                break
            if self.n_events >= max_events:
                truncated = True
                break
            self.advance(self.t, t_next - self.t)
            self.t = t_next
            self.n_events += 1
            if t_comp <= t_ev:
                self.dirty.add(int(self.placement[s]))
                self.complete(s, t_next)
            else:
                self.timed(t_next)
            self.realloc(t_next)
        return self.result(truncated)

    def result(self, truncated):
        n = {RAN: 0, LARGE: 0, SMALL: 0}
        ok = {RAN: 0, LARGE: 0, SMALL: 0}
        for i, r in enumerate(self.req):
            n[r[1]] += 1
            f = self.finish[i]
            ok[r[1]] += bool(f >= 0 and (f - r[2]) <= r[3])
        counts = {"ran": (n[RAN], n[RAN] - ok[RAN]),
                  "large_ai": (n[LARGE], n[LARGE] - ok[LARGE]),
                  "small_ai": (n[SMALL], n[SMALL] - ok[SMALL])}
        ai = (n[LARGE] + n[SMALL], n[LARGE] + n[SMALL] - ok[LARGE] - ok[SMALL])
        counts["ai"] = ai
        counts["overall"] = (ai[0] + n[RAN], ai[1] + counts["ran"][1])
        return {"n_events": self.n_events, "n_requests": len(self.req),
                "truncated": truncated, "infeasible_events": self.infeasible,
                "counts": counts, "migrations": list(self.migrations),
                "finish": self.finish.copy()}


def simulate(dep, requests, horizon, method, epoch_interval=5.0,
             core_dtype=np.float64):
    """Run one replica to its end; returns its outcome record."""
    return Replica(dep, requests, horizon, method, epoch_interval,
                   core_dtype).run()
