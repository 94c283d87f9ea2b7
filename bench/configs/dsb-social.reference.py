"""Plain reference for the DeathStarBench social-network configuration,
in NumPy.

It runs the same simulation as the engine, one tick at a time, from the
same seed, with none of the engine's code, read from the configuration's
own documents (paper Fig 3 registry: services, APIs with their call
trees, payloads, instance groups, the node list):

* Generation: closed-loop clients (paper Alg 1) pick an API by weight and
  fire; each admitted request spawns a root call at its API's tree's
  root, addressed round-robin (first come, first served within the
  tick's wave) to a replica and sent over the fabric with a Gaussian
  payload;
* Transit: transfers share each host NIC's egress and ingress max-min
  fairly (progressive water-filling, two freeze rounds, then one
  conservative fill); a transfer that arrives joins the waiting queue;
* Dispatch: a waiting call runs on the replica it was addressed to, or
  else round-robin in slot order;
* Execute: equal time slices of the instance's MIPS, finish times within
  the tick;
* Derive: a finished call spawns one call per callee that its request's
  API calls from its service, at the parent's finish time, addressed
  like root calls; a call made with probability p < 1 (a cache miss) is
  made where the tick's uniform draw for that (parent slot, callee slot)
  falls under p; a call to a replica on the caller's host skips the
  fabric.  A service's callee slots are its own ``calls``, then the
  callees it gains from each API's tree, in the order the APIs list
  them;
* Response: a request's response is its last call's finish time minus its
  arrival.

The configuration has no faults and no autoscaler, so neither stage is
simulated, and a replica never changes.

The seeded stream is the jobs' data, drawn with ``jax.random`` as the
engine draws it and on the device the jobs ran on (JAX's default
device): a v5e's normal draws differ from the CPU's in nine of ten
values, by up to 301 units in the last place (its inverse error function
rounds otherwise), and a call length one unit off can move a finish to
another tick, after which every later cache miss lands on another call.
Each tick splits the carried key seven ways (carry,
generation, spawn, balancer, successors, fabric at generation, fabric at
derive); generation splits its key into (API, wait), each fabric key
into (balancer, payload); the calls' draws come from the successors' key
folded with 1.

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
INST_ON = 1
SAT_REL = 1e-5               # a NIC port is saturated within this share
MIN_PAYLOAD_MB = 1e-6
DEFAULT_PAYLOAD_MB = 0.01
WATERFILL_ROUNDS = 2
PLACEMENT_SPREAD = "spread"

# Outputs of each job the comparison reads besides the responses.
READBACK = ("spawned",)

LIMITS = {
    "admitted_ticks_differing": 0,
    "finished_requests_differing": 0,
    "calls_per_request_differing": 0,
    "response_gap_ms": 1.0,
}

# Knobs whose behaviour the reference leaves out; it refuses a
# configuration that sets any of them.  (Telemetry only observes: the
# simulated outputs are the same with it on.)
_UNMODELLED = ("egress_shaping", "lb_policy", "share_policy",
               "max_concurrent", "k_fire", "migration_enabled")


@functools.lru_cache(maxsize=4)
def _drawer(T: int, Nc: int, K: int, C: int, D: int):
    import jax

    r = jax.random

    def body(key, _):
        keys = r.split(key, 7)
        k_api, k_wait = r.split(keys[1], 2)
        _, k_gpay = r.split(keys[5], 2)
        _, k_dpay = r.split(keys[6], 2)
        return keys[0], (r.uniform(k_api, (Nc,)), r.uniform(k_wait, (Nc,)),
                         r.normal(keys[2], (K,)), r.normal(k_gpay, (K,)),
                         r.normal(keys[4], (C,)), r.normal(k_dpay, (C,)),
                         r.uniform(r.fold_in(keys[4], 1), (C, D)))

    return jax.jit(lambda key: jax.lax.scan(body, key, None, length=T)[1])


def seeded_draws(seed: int, T: int, Nc: int, K: int, C: int, D: int):
    """Every draw of a job, per tick, on JAX's default device."""
    import jax

    out = _drawer(T, Nc, K, C, D)(jax.random.PRNGKey(seed))
    return [np.asarray(x) for x in out]


def _callee(c) -> tuple:
    """(callee, p) of an entry of a ``calls`` list: a name means p = 1."""
    return (c, 1.0) if isinstance(c, str) else (c["to"], c.get("p", 1.0))


def model(config: dict, traffic: dict) -> dict:
    """The deployment, read from the configuration's documents."""
    p = dict(config["params"], **traffic.get("params", {}))
    bad = [k for k in _UNMODELLED if k in p]
    if p.get("network") != "fabric" or p.get("scaling_policy", 0) != 0 \
            or p.get("faults", "none") != "none" \
            or config.get("placement") != PLACEMENT_SPREAD or bad:
        raise ValueError(f"the reference models fabric, no faults, no "
                         f"autoscaler and spread placement only; asked for "
                         f"{bad}")
    f32 = np.float32
    svcs = config["app"]["services"]
    apis = config["app"]["apis"]
    names = [s["name"] for s in svcs]
    ix = {n: i for i, n in enumerate(names)}
    S, A = len(names), len(apis)
    # each API's calls {(caller, callee): p}: its tree, else the services'
    own = {(s["name"], to): pr for s in svcs
           for to, pr in map(_callee, s.get("calls", []))}
    routes = [{(src, to): pr for src, cs in a["tree"]["calls"].items()
               for to, pr in map(_callee, cs)} if "tree" in a else own
              for a in apis]
    callees = [[] for _ in range(S)]
    for src, to in list(own) + [e for r in routes for e in r]:
        if ix[to] not in callees[ix[src]]:
            callees[ix[src]].append(ix[to])
    D = max([config["caps"]["d_max"]] + [len(c) for c in callees])
    succ = np.full((S, D), -1, np.int64)
    for i, c in enumerate(callees):
        succ[i, :len(c)] = c
    route = np.zeros((A, S, D), f32)
    for a, r in enumerate(routes):
        for (src, to), pr in r.items():
            route[a, ix[src], callees[ix[src]].index(ix[to])] = pr
    pay_mean = np.full((S, D), DEFAULT_PAYLOAD_MB, f32)
    pay_std = f32(0.1) * pay_mean
    for i, s in enumerate(svcs):
        for callee, mb in s.get("payloads", {}).items():
            d = callees[i].index(ix[callee])
            pay_mean[i, d] = mb
            pay_std[i, d] = 0.1 * mb
    w = np.asarray(np.asarray([a["weight"] for a in apis], f32), np.float64)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    api_pay = np.asarray([a.get("payload", DEFAULT_PAYLOAD_MB)
                          for a in apis], f32)
    groups = {g["labels"][0]: g for g in config["instances"]["instances"]}
    tmpl = [groups[n] for n in names]
    return dict(
        p=p, caps=config["caps"], S=S, D=D, succ=succ, route=route,
        len_mean=np.asarray([s["mi"] for s in svcs], f32),
        len_std=np.asarray([s["mi_std"] for s in svcs], f32),
        pay_mean=pay_mean, pay_std=pay_std, api_cdf=cdf.astype(f32),
        api_entry=np.asarray([ix[a["tree"]["root"] if "tree" in a
                                 else a["entry"]] for a in apis], np.int64),
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
         self.z_dpay, self.u_call) = seeded_draws(seed, self.T, self.Nc,
                                                  self.K, self.C, m["D"])
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
        self.i_nexec = np.zeros(I, np.int64)
        self.iof = np.full((S, self.Rmax), -1, np.int64)
        self.reps = np.zeros(S, np.int64)
        self.rr = np.zeros(S, np.int64)
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
                used_m[v] += m["t_mips"][s]
                used_r[v] += m["t_ram"][s]
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
        self.c_rem = np.where(ex, np.maximum(rem - prog, ft(0.0)), rem)
        r_fin = self.c_req[fin]
        np.maximum.at(self.r_fin, r_fin, tfin[fin])
        np.subtract.at(self.r_out, r_fin, 1)
        self.i_nexec = self.i_nexec - np.bincount(inst[fin],
                                                  minlength=self.I)
        info = (fin, self.c_svc.copy(), self.c_req.copy(),
                self.c_depth.copy(), self.c_inst.copy())
        self.c_status[fin] = CL_FREE
        self.c_inst[fin] = -1
        return info, r_fin

    def derive(self, t, info):
        """The calls each finished call's request makes from its
        service, in slot order."""
        m, ft, dtype = self.m, self.ft, self.dt_
        fin, svc, req, depth, inst = info
        parent = np.flatnonzero(fin)
        p = m["route"][self.r_api[req[parent]], svc[parent]]
        made = (m["succ"][svc[parent]] >= 0) \
            & (self.u_call[t][parent] < p)
        cc, dd = np.nonzero(made)
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

    def run(self) -> dict:
        for t in range(self.T):
            self.generation(t)
            self.transit()
            self.dispatch()
            info, r_fin = self.execute()
            self.derive(t, info)
            self.response(r_fin)
            self.time = self.ft(self.time + self.dt)
        return dict(response=self.r_resp.astype(np.float64),
                    count=self.count, generated=self.admitted,
                    spawned=self.r_spawned)


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
