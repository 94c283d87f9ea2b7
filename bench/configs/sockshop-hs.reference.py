"""Plain reference for the SockShop-HS configuration, in NumPy.

It runs the same simulation as the engine, one tick at a time, from the
same seed, with none of the engine's code, read from the configuration's
own documents (paper Fig 3 registry: services, calls, APIs, payloads,
instance groups, the node list):

* Generation: closed-loop clients (paper Alg 1) pick an API by weight and
  fire; each admitted request spawns a root call at its API's entry
  service, addressed round-robin (first come, first served within the
  tick's wave) to a replica and sent over the fabric with a Gaussian
  payload;
* Disruption: the configuration's fault rates are the defaults (no
  crash, kill, brownout, partition, timeout, breaker or ejection ever
  fires), so the phase changes nothing and is not simulated;
* Transit: transfers share each host NIC's egress and ingress max-min
  fairly (progressive water-filling, two freeze rounds, then one
  conservative fill); a transfer that arrives joins the waiting queue;
* Dispatch: a waiting call runs on the replica it was addressed to, or
  else round-robin in slot order;
* Execute: equal time slices of the instance's MIPS, finish times within
  the tick;
* Derive: a finished call spawns one call per callee of its service, at
  the parent's finish time, addressed like root calls; a call to a
  replica on the caller's host skips the fabric;
* Response: a request's response is its last call's finish time minus its
  arrival;
* Scaling: every ``scale_interval`` ticks the HS autoscaler (paper Alg 4)
  adds a replica of each service whose replicas' utilization average is
  over ``hs_util_hi`` (on the node with most free MIPS) and drains the
  newest replica of each one under ``hs_util_lo``.

The seeded stream is drawn with ``jax.random`` on the CPU as the engine
draws it: each tick splits the carried key ten ways (carry, generation,
spawn, balancer, successors, fabric at generation, fabric at derive, and
three fault streams); generation splits its key into (API, wait), each
fabric key into (balancer, payload).

``dtype`` is the precision of every real number of the simulation:
``float32`` as the configuration states, ``bfloat16`` for the control.
"""
from __future__ import annotations

import functools
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from cnsbench.jobs import point_traffics  # noqa: E402

CL_FREE, CL_WAITING, CL_EXEC, CL_TRANSIT = 0, 1, 2, 3
INST_FREE, INST_ON, INST_DRAIN = 0, 1, 2
SAT_REL = 1e-5               # a NIC port is saturated within this share
MIN_PAYLOAD_MB = 1e-6
DEFAULT_PAYLOAD_MB = 0.01
WATERFILL_ROUNDS = 2
PLACEMENT_SPREAD = "spread"

# Outputs of each job the comparison reads besides the responses.
READBACK = ("spawned", "replicas", "failed_requests")

LIMITS = {
    "admitted_ticks_differing": 0,
    "finished_requests_differing": 0,
    "calls_per_request_differing": 0,
    "replica_counts_differing": 0,
    "failed_requests": 0,
    "response_gap_ms": 1.0,
}

# Knobs whose behaviour the reference leaves out; it refuses a
# configuration that sets any of them.  (Telemetry only observes: the
# simulated outputs are the same with it on.)
_UNMODELLED = ("host_mtbf_s", "inst_kill_rate", "nic_degrade_rate",
               "retry_timeout_s", "cb_err_thresh", "host_slow_mtbf_s",
               "zone_fault_rate", "zone_slow_rate", "zone_partition_rate",
               "eject_err_thresh", "eject_lat_factor", "egress_shaping",
               "lb_policy", "share_policy", "max_concurrent", "k_fire",
               "migration_enabled")


@functools.lru_cache(maxsize=4)
def _drawer(T: int, Nc: int, K: int, C: int):
    import jax

    r = jax.random

    def body(key, _):
        keys = r.split(key, 10)
        k_api, k_wait = r.split(keys[1], 2)
        _, k_gpay = r.split(keys[5], 2)
        _, k_dpay = r.split(keys[6], 2)
        return keys[0], (r.uniform(k_api, (Nc,)), r.uniform(k_wait, (Nc,)),
                         r.normal(keys[2], (K,)), r.normal(k_gpay, (K,)),
                         r.normal(keys[4], (C,)), r.normal(k_dpay, (C,)))

    return jax.jit(lambda key: jax.lax.scan(body, key, None, length=T)[1])


def seeded_draws(seed: int, T: int, Nc: int, K: int, C: int):
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        out = _drawer(T, Nc, K, C)(jax.random.PRNGKey(seed))
        return [np.asarray(x) for x in out]


def model(config: dict, traffic: dict) -> dict:
    """The deployment, read from the configuration's documents."""
    p = dict(config["params"], **traffic.get("params", {}))
    bad = [k for k in _UNMODELLED if k in p]
    if p.get("network") != "fabric" or p.get("scaling_policy") != 1 \
            or config.get("placement") != PLACEMENT_SPREAD or bad:
        raise ValueError(f"the reference models fabric, default chaos, HS "
                         f"and spread placement only; asked for {bad}")
    f32 = np.float32
    svcs = config["app"]["services"]
    names = [s["name"] for s in svcs]
    ix = {n: i for i, n in enumerate(names)}
    S = len(names)
    D = max([config["caps"]["d_max"]] + [len(s["calls"]) for s in svcs])
    succ = np.full((S, D), -1, np.int64)
    pay_mean = np.full((S, D), DEFAULT_PAYLOAD_MB, f32)
    pay_std = f32(0.1) * pay_mean
    for i, s in enumerate(svcs):
        for d, callee in enumerate(s["calls"]):
            succ[i, d] = ix[callee]
        for callee, mb in s.get("payloads", {}).items():
            d = s["calls"].index(callee)
            pay_mean[i, d] = mb
            pay_std[i, d] = 0.1 * mb
    apis = config["app"]["apis"]
    w = np.asarray(np.asarray([a["weight"] for a in apis], f32), np.float64)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    api_pay = np.asarray([a.get("payload", DEFAULT_PAYLOAD_MB)
                          for a in apis], f32)
    groups = {g["labels"][0]: g for g in config["instances"]["instances"]}
    tmpl = [groups[n] for n in names]
    return dict(
        p=p, caps=config["caps"], S=S, D=D, succ=succ,
        len_mean=np.asarray([s["mi"] for s in svcs], f32),
        len_std=np.asarray([s["mi_std"] for s in svcs], f32),
        pay_mean=pay_mean, pay_std=pay_std, api_cdf=cdf.astype(f32),
        api_entry=np.asarray([ix[a["entry"]] for a in apis], np.int64),
        api_pay_mean=api_pay, api_pay_std=f32(0.1) * api_pay,
        t_mips=np.asarray([g["requests"]["share"] for g in tmpl], f32),
        t_ram=np.asarray([g["requests"]["ram"] for g in tmpl], f32),
        t_reps=[int(g["replicas"]) for g in tmpl],
        vm_mips=np.asarray(config["vm_mips"], f32),
        vm_ram=np.asarray(config["vm_ram"], f32))


class _Sim:
    """One job's state and its tick."""

    def __init__(self, m: dict, seed: int, dtype):
        self.m, self.dt_ = m, dtype
        ft = self.ft = np.dtype(dtype).type
        p, caps = m["p"], m["caps"]
        self.T = int(p["n_ticks"])
        self.Nc, self.R = caps["n_clients"], caps["max_requests"]
        self.C, self.I, self.V = (caps["max_cloudlets"],
                                  caps["max_instances"], caps["n_vms"])
        self.Rmax = caps["max_replicas"]
        self.K = min(self.Nc, self.C)
        (self.u_api, self.u_wait, self.z_gen, self.z_gpay, self.z_der,
         self.z_dpay) = seeded_draws(seed, self.T, self.Nc, self.K, self.C)
        self.dt = ft(p["dt"])
        self.time = ft(0.0)
        self.n_clients = min(int(p["n_clients"]), self.Nc)
        self.wait = np.zeros(self.Nc, np.int64)
        self.count = 0
        R, C, I, S = self.R, self.C, self.I, m["S"]
        self.r_api = np.full(R, -1, np.int64)
        self.r_arr = np.full(R, -1.0, dtype)
        self.r_fin = np.zeros(R, dtype)
        self.r_resp = np.full(R, -1.0, dtype)
        self.r_out = np.zeros(R, np.int64)
        self.r_spawned = np.zeros(R, np.int64)
        self.c_status = np.zeros(C, np.int64)
        self.c_req = np.full(C, -1, np.int64)
        self.c_svc = np.full(C, -1, np.int64)
        self.c_inst = np.full(C, -1, np.int64)
        self.c_depth = np.zeros(C, np.int64)
        self.c_src = np.full(C, -1, np.int64)
        self.c_rem = np.zeros(C, dtype)
        self.c_bytes = np.zeros(C, dtype)
        self.i_status = np.zeros(I, np.int64)
        self.i_svc = np.full(I, -1, np.int64)
        self.i_host = np.full(I, -1, np.int64)
        self.i_mips = np.zeros(I, dtype)
        self.i_ram = np.zeros(I, dtype)
        self.i_nexec = np.zeros(I, np.int64)
        self.i_util = np.zeros(I, dtype)
        self.iof = np.full((S, self.Rmax), -1, np.int64)
        self.reps = np.zeros(S, np.int64)
        self.rr = np.zeros(S, np.int64)
        self.vm_mips = m["vm_mips"].astype(dtype)
        self.vm_ram = m["vm_ram"].astype(dtype)
        self.vm_used = np.zeros(self.V, dtype)
        self.vm_ram_used = np.zeros(self.V, dtype)
        self._place()
        self.admitted = np.zeros(self.T, np.int64)
        cap = ft(ft(p.get("nic_egress_mbps", 1000.0)) * ft(0.125))
        cap_i = ft(ft(p.get("nic_ingress_mbps", 1000.0)) * ft(0.125))
        self.cap_e = np.full(self.V, cap, dtype)
        self.cap_i = np.full(self.V, cap_i, dtype)

    def _place(self):
        """Topology spread: replica k of the deployment goes to the first
        node, cycling from node k, that fits it."""
        m = self.m
        used_m = np.zeros(self.V)
        used_r = np.zeros(self.V)
        slot = 0
        for s in range(m["S"]):
            for r in range(m["t_reps"][s]):
                for v in np.roll(np.arange(self.V), -slot):
                    if (m["vm_mips"][v] - used_m[v] >= m["t_mips"][s]
                            and m["vm_ram"][v] - used_r[v] >= m["t_ram"][s]):
                        break
                else:
                    raise ValueError(f"service {s} replica {r} fits no node")
                self.i_status[slot] = INST_ON
                self.i_svc[slot], self.i_host[slot] = s, v
                self.i_mips[slot] = m["t_mips"][s]
                self.i_ram[slot] = m["t_ram"][s]
                used_m[v] += m["t_mips"][s]
                used_r[v] += m["t_ram"][s]
                self.vm_used[v] = self.ft(self.vm_used[v] + self.i_mips[slot])
                self.vm_ram_used[v] = self.ft(self.vm_ram_used[v]
                                              + self.i_ram[slot])
                self.iof[s, r] = slot
                self.reps[s] += 1
                slot += 1

    # --- shared pieces -------------------------------------------------
    def _address(self, svc, live):
        """Round-robin replica of each new call, first come first served
        within the wave; -1 where no replica is on.  Advances the
        cursors by the calls addressed."""
        S = self.m["S"]
        offset = np.zeros(len(svc), np.int64)
        seen = np.zeros(S, np.int64)
        for k in np.flatnonzero(live):
            offset[k] = seen[svc[k]]
            seen[svc[k]] += 1
        reps = self.reps[svc]
        rank = (self.rr[svc] + offset) % np.maximum(reps, 1)
        tgt = self.iof[svc, np.minimum(rank, self.Rmax - 1)]
        ok = live & (reps > 0) & (tgt >= 0)
        ok &= self.i_status[np.maximum(tgt, 0)] == INST_ON
        counts = np.bincount(svc[ok], minlength=S)
        self.rr = (self.rr + counts) % np.maximum(self.reps, 1)
        return np.where(ok, tgt, -1)

    def _spawn(self, n_want, svc, req, depth, tgt, src, rem, nbytes):
        """Write a wave into the lowest free slots (in order)."""
        free = np.flatnonzero(self.c_status == CL_FREE)
        n = min(len(free), n_want)
        dst = free[:n]
        transit = tgt[:n] >= 0
        if src is not None:
            transit &= ~((src[:n] >= 0) & (src[:n] == self.i_host[
                np.maximum(tgt[:n], 0)]))
        self.c_status[dst] = np.where(transit, CL_TRANSIT, CL_WAITING)
        self.c_req[dst] = req[:n]
        self.c_svc[dst] = svc[:n]
        self.c_inst[dst] = tgt[:n]
        self.c_depth[dst] = depth[:n]
        self.c_src[dst] = -1 if src is None else np.where(transit, src[:n],
                                                          -1)
        self.c_rem[dst] = rem[:n]
        self.c_bytes[dst] = np.where(transit, nbytes[:n], self.ft(0.0))
        np.add.at(self.r_out, req[:n], 1)
        np.add.at(self.r_spawned, req[:n], 1)

    # --- phases ----------------------------------------------------------
    def generation(self, t):
        m, ft, dtype = self.m, self.ft, self.dt_
        p = m["p"]
        n_active = min(self.n_clients,
                       int(np.floor(ft(p["spawn_rate"]) * self.time)) + 1)
        fired = (np.arange(self.Nc) < n_active) & (self.wait <= 0)
        api = np.minimum(np.searchsorted(m["api_cdf"], self.u_api[t]),
                         len(m["api_cdf"]) - 1)
        lo, hi = ft(p["wait_lo"]), ft(p["wait_hi"])
        wait_s = lo + (hi - lo) * self.u_wait[t].astype(dtype)
        prop = np.maximum(np.round(wait_s / self.dt), 1).astype(np.int64)
        rank = np.cumsum(fired) - 1
        in_budget = fired & (rank < self.K)
        has_slot = in_budget & (self.count + rank < self.R)
        n = int(has_slot.sum())
        self.wait = np.where(in_budget, prop, np.where(
            fired, 0, np.maximum(self.wait - 1, 0)))
        req = self.count + np.arange(n)
        a = api[np.flatnonzero(has_slot)]
        self.r_api[req] = a
        self.r_arr[req] = self.time
        svc = m["api_entry"][a]
        live = np.arange(n) < min(n, int((self.c_status == CL_FREE).sum()))
        z, zp = self.z_gen[t][:n].astype(dtype), self.z_gpay[t][:n]
        rem = np.maximum(m["len_mean"][svc].astype(dtype)
                         + m["len_std"][svc].astype(dtype) * z, ft(1.0))
        tgt = self._address(svc, live)
        nbytes = np.maximum(m["api_pay_mean"][a].astype(dtype)
                            + m["api_pay_std"][a].astype(dtype)
                            * zp.astype(dtype), ft(MIN_PAYLOAD_MB))
        self._spawn(int(live.sum()), svc, req, np.zeros(n, np.int64), tgt,
                    None, rem, nbytes)
        self.count += n
        self.admitted[t] = n

    def _waterfill(self, src, dst, live):
        ft, dtype = self.ft, self.dt_
        rate = np.zeros(len(src), dtype)
        rem_e, rem_i = self.cap_e.copy(), self.cap_i.copy()
        inf = ft(np.inf)

        def occupancy(lv):
            n_e = np.bincount(src[lv & (src >= 0)], minlength=self.V)
            n_i = np.bincount(dst[lv], minlength=self.V)
            return n_e.astype(dtype), n_i.astype(dtype)

        for _ in range(WATERFILL_ROUNDS):
            n_e, n_i = occupancy(live)
            share_e = rem_e / np.maximum(n_e, ft(1.0))
            share_i = rem_i / np.maximum(n_i, ft(1.0))
            lam = min(np.min(np.where(n_e > 0, share_e, inf)),
                      np.min(np.where(n_i > 0, share_i, inf)))
            lam = ft(max(lam, ft(0.0))) if np.isfinite(lam) else ft(0.0)
            rate = np.where(live, rate + lam, rate)
            rem_e = rem_e - lam * n_e
            rem_i = rem_i - lam * n_i
            sat_e = (n_e > 0) & (rem_e <= ft(SAT_REL) * self.cap_e)
            sat_i = (n_i > 0) & (rem_i <= ft(SAT_REL) * self.cap_i)
            frozen = ((src >= 0) & sat_e[np.maximum(src, 0)]) \
                | sat_i[np.maximum(dst, 0)]
            live = live & ~frozen
        n_e, n_i = occupancy(live)
        share_e = rem_e / np.maximum(n_e, ft(1.0))
        share_i = rem_i / np.maximum(n_i, ft(1.0))
        fill = np.minimum(np.where(src >= 0, share_e[np.maximum(src, 0)],
                                   inf), share_i[np.maximum(dst, 0)])
        return np.where(live, rate + np.maximum(fill, ft(0.0)), rate)

    def transit(self):
        ft = self.ft
        active = self.c_status == CL_TRANSIT
        dst = np.where(active & (self.c_inst >= 0),
                       self.i_host[np.maximum(self.c_inst, 0)], -1)
        flowing = active & (dst >= 0)
        rate = self._waterfill(self.c_src, dst, flowing)
        rate = np.where(flowing, rate, ft(0.0))
        prog = rate * self.dt
        arrived = (active & (self.c_bytes <= prog) & (rate > 0)) \
            | (active & (dst < 0))
        self.c_bytes = np.where(arrived, ft(0.0), np.where(
            active, np.maximum(self.c_bytes - prog, ft(0.0)), self.c_bytes))
        self.c_status[arrived] = CL_WAITING

    def dispatch(self):
        C = self.C
        waiting = self.c_status == CL_WAITING
        svc = np.where(waiting, self.c_svc, 0)
        reps = self.reps[svc]
        rank = (self.rr[svc] + np.arange(C)) % np.maximum(reps, 1)
        tgt = self.iof[svc, np.minimum(rank, self.Rmax - 1)]
        ok = waiting & (reps > 0) & (tgt >= 0)
        ok &= self.i_status[np.maximum(tgt, 0)] == INST_ON
        pre = self.c_inst
        pre_s = np.maximum(pre, 0)
        use_pre = (waiting & (pre >= 0) & (self.i_status[pre_s] == INST_ON)
                   & (self.i_svc[pre_s] == self.c_svc))
        tgt = np.where(use_pre, pre, tgt)
        admit = ok | use_pre
        lb = admit & ~use_pre
        s_lb = self.i_svc[tgt[lb]]
        counts = np.bincount(s_lb[s_lb >= 0], minlength=self.m["S"])
        self.rr = (self.rr + counts) % np.maximum(self.reps, 1)
        self.c_status[admit] = CL_EXEC
        self.c_inst[admit] = tgt[admit]
        np.add.at(self.i_nexec, tgt[admit], 1)

    def execute(self):
        ft, dtype = self.ft, self.dt_
        ex = self.c_status == CL_EXEC
        inst = np.where(ex, self.c_inst, 0)
        rate = np.where(ex, self.i_mips[inst] / np.maximum(
            self.i_nexec[inst].astype(dtype), ft(1e-9)), ft(0.0))
        prog = rate * self.dt
        rem = self.c_rem
        fin = ex & (rem <= prog) & (rate > 0)
        tfin = np.clip(self.time + rem / np.maximum(rate, ft(1e-9)),
                       self.time, self.time + self.dt)
        consumed = np.where(ex, np.minimum(prog, rem), ft(0.0))
        self.c_rem = np.where(ex, np.maximum(rem - prog, ft(0.0)), rem)
        used = np.zeros(self.I, dtype)
        np.add.at(used, inst[ex], consumed[ex] / self.dt)
        util = np.where(self.i_mips > 0, used / np.maximum(
            self.i_mips, ft(1e-9)), ft(0.0))
        a = ft(self.m["p"].get("util_ema", 0.2))
        ema = np.where(self.i_status != INST_FREE,
                       a * util + (ft(1.0) - a) * self.i_util, ft(0.0))
        r_fin = self.c_req[fin]
        np.maximum.at(self.r_fin, r_fin, tfin[fin])
        np.subtract.at(self.r_out, r_fin, 1)
        fin_per = np.bincount(inst[fin], minlength=self.I)
        self.i_nexec = self.i_nexec - fin_per
        done = (self.i_status == INST_DRAIN) & (self.i_nexec == 0)
        for i in np.flatnonzero(done):
            v = self.i_host[i]
            self.vm_used[v] = ft(self.vm_used[v] - self.i_mips[i])
            self.vm_ram_used[v] = ft(self.vm_ram_used[v] - self.i_ram[i])
        self.i_status[done] = INST_FREE
        self.i_svc[done] = -1
        self.i_host[done] = -1
        self.i_mips[done] = 0
        self.i_ram[done] = 0
        self.i_util = np.where(done, ft(0.0), ema)
        info = (fin, self.c_svc.copy(), self.c_req.copy(),
                self.c_depth.copy(), self.c_inst.copy())
        self.c_status[fin] = CL_FREE
        self.c_inst[fin] = -1
        return info, r_fin

    def derive(self, t, info):
        """Calls to each callee of each finished call, in slot order."""
        m, ft, dtype = self.m, self.ft, self.dt_
        fin, svc, req, depth, inst = info
        parent = np.flatnonzero(fin)
        cc, dd = np.nonzero(m["succ"][svc[parent]] >= 0)
        n = min(len(cc), self.C)
        par, dd = parent[cc[:n]], dd[:n]
        c_svc = m["succ"][svc[par], dd]
        live = np.arange(n) < int((self.c_status == CL_FREE).sum())
        z = self.z_der[t][:n].astype(dtype)
        rem = np.maximum(m["len_mean"][c_svc].astype(dtype)
                         + m["len_std"][c_svc].astype(dtype) * z, ft(1.0))
        tgt = self._address(c_svc, live)
        psvc = svc[par]
        nbytes = np.maximum(
            m["pay_mean"][psvc, dd].astype(dtype)
            + m["pay_std"][psvc, dd].astype(dtype)
            * self.z_dpay[t][:n].astype(dtype), ft(MIN_PAYLOAD_MB))
        pin = inst[par]
        src = np.where(pin >= 0, self.i_host[np.maximum(pin, 0)], -1)
        self._spawn(int(live.sum()), c_svc, req[par], depth[par] + 1, tgt,
                    src, rem, nbytes)

    def response(self, r_fin):
        cand = np.unique(r_fin)
        done = cand[(self.r_out[cand] == 0) & (self.r_spawned[cand] > 0)
                    & (self.r_resp[cand] < 0) & (self.r_arr[cand] >= 0)]
        self.r_resp[done] = self.r_fin[done] - self.r_arr[done]

    def scaling(self):
        ft, S = self.ft, self.m["S"]
        p = self.m["p"]
        on = self.i_status == INST_ON
        tot = np.zeros(S, self.dt_)
        np.add.at(tot, self.i_svc[on], self.i_util[on])
        cnt = np.bincount(self.i_svc[on], minlength=S).astype(self.dt_)
        util = tot / np.maximum(cnt, ft(1.0))
        out = (util > ft(p["hs_util_hi"])) & (self.reps >= 1) \
            & (self.reps < self.Rmax)
        into = (util < ft(p["hs_util_lo"])) & (self.reps > 1)
        for s in range(S):
            if out[s]:
                self._scale_out(s)
            if into[s]:
                self._scale_in(s)

    def _scale_out(self, s):
        m, ft = self.m, self.ft
        free_slots = np.flatnonzero(self.i_status == INST_FREE)
        free = self.vm_mips - self.vm_used
        v = int(np.argmax(free))
        if not len(free_slots) or free[v] < m["t_mips"][s] \
                or self.vm_ram[v] - self.vm_ram_used[v] < m["t_ram"][s]:
            return
        i = free_slots[0]
        self.i_status[i], self.i_svc[i], self.i_host[i] = INST_ON, s, v
        self.i_mips[i], self.i_ram[i] = m["t_mips"][s], m["t_ram"][s]
        self.i_util[i] = ft(0.5)
        self.vm_used[v] = ft(self.vm_used[v] + self.i_mips[i])
        self.vm_ram_used[v] = ft(self.vm_ram_used[v] + self.i_ram[i])
        self.iof[s, self.reps[s]] = i
        self.reps[s] = min(self.reps[s] + 1, self.Rmax)

    def _scale_in(self, s):
        n = self.reps[s]
        slots = self.iof[s]
        on = (np.arange(self.Rmax) < n) & (slots >= 0) \
            & (self.i_status[np.maximum(slots, 0)] == INST_ON)
        if not on.any():
            return
        rank = int(np.flatnonzero(on)[-1])
        if rank < 1:
            return
        self.i_status[slots[rank]] = INST_DRAIN
        last = int(np.clip(n - 1, 0, self.Rmax - 1))
        self.iof[s, rank] = -1 if rank == last else self.iof[s, last]
        self.iof[s, last] = -1
        self.reps[s] = max(n - 1, 0)

    def run(self) -> dict:
        interval = int(self.m["p"]["scale_interval"])
        for t in range(self.T):
            self.generation(t)
            self.transit()
            self.dispatch()
            info, r_fin = self.execute()
            self.derive(t, info)
            self.response(r_fin)
            if t % interval == interval - 1:
                self.scaling()
            self.time = self.ft(self.time + self.dt)
        return dict(response=self.r_resp.astype(np.float64),
                    count=self.count, generated=self.admitted,
                    spawned=self.r_spawned, replicas=self.reps.copy(),
                    failed_requests=0)


def run(config: dict, traffic: dict, seed: int, dtype=np.float32) -> dict:
    """One job of the cell, as the reference computes it."""
    return _Sim(model(config, traffic), seed, dtype).run()


def gaps(ref: dict, got: dict) -> dict:
    """The compared numbers of one job (the program's outputs ``got``)."""
    r = np.asarray(ref["response"], np.float64)
    g = np.asarray(got["response"], np.float64)
    both = (r >= 0) & (g >= 0)
    gap = float(np.abs(r[both] - g[both]).max() * 1e3) if both.any() \
        else 0.0
    n = int(got["count"])
    return {
        "admitted_ticks_differing": int(
            (np.asarray(ref["generated"]) != np.asarray(got["generated"]))
            .sum()),
        "finished_requests_differing": int(((r >= 0) != (g >= 0)).sum()),
        "calls_per_request_differing": int(
            (np.asarray(ref["spawned"])[:n]
             != np.asarray(got["spawned"])[:n]).sum()),
        "replica_counts_differing": int(
            (np.asarray(ref["replicas"]) != np.asarray(got["replicas"]))
            .sum()),
        "failed_requests": int(got["failed_requests"]),
        "response_gap_ms": gap,
    }


def compare(config: dict, traffic: dict, jobs: list,
            dtype=np.float32) -> dict:
    """The worst of each compared number over ``jobs`` (the timed jobs'
    ``JobOut``s), each simulated point against its own run."""
    worst: dict = {}
    for job in jobs:
        for out, point in zip(job.outputs, point_traffics(traffic)):
            ref = run(config, point, job.seed, dtype)
            for k, v in gaps(ref, out).items():
                worst[k] = max(worst.get(k, v), v)
    return worst
