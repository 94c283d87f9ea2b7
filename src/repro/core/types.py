"""Core data structures for the CloudNativeSim tensor-DES engine.

The paper's Java object graph (Request / RpcCloudlet / Instance / VM /
Service) is re-expressed as fixed-shape tensor pools so that one simulator
tick is a fused dataflow update and the whole run is a single
``jax.lax.scan``.  See DESIGN.md §2 for the adaptation rationale.

Conventions
-----------
* All pools use int32 / float32 (JAX default x64-disabled).
* ``-1`` is the universal "null id" (no instance, no service, padding).
* Pools are *fixed capacity*; requests are append-only, cloudlets use an
  active-set buffer with free-slot recycling (finished cloudlets fold their
  statistics into per-request / per-instance aggregates and free the slot —
  the paper's "finished queue" is an aggregate, not an archive).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import streams

# --------------------------------------------------------------------------
# Cloudlet status codes (paper §4.2: waiting / execution / finished queues).
# --------------------------------------------------------------------------
CL_FREE = 0       # slot unused (or folded into the "finished" aggregate)
CL_WAITING = 1    # in the waiting queue
CL_EXEC = 2       # in the execution queue
CL_TRANSIT = 3    # RPC payload in flight on the network fabric (§6)

# Instance status codes.
INST_FREE = 0     # slot unused
INST_ON = 1       # active, receiving cloudlets
INST_DRAIN = 2    # scale-in requested: no new cloudlets, frees when empty
INST_DOWN = 3     # crashed (host down or pod killed): no dispatch, in-flight
#                   work failed; restarts via MTTR once its host is up (§7)


@dataclasses.dataclass(frozen=True)
class SimCaps:
    """Static pool capacities (hashable → safe to close over in jit)."""

    n_clients: int = 128          # Nc upper bound (client pool size)
    max_requests: int = 4096      # append-only request pool
    max_cloudlets: int = 8192     # ACTIVE cloudlet buffer (waiting+exec)
    max_instances: int = 64       # instance pool (incl. head-room for HS)
    n_vms: int = 8
    d_max: int = 4                # max out-degree of any service node
    max_replicas: int = 8         # per-service replica cap (HS)
    k_fire: int = 0               # max requests admitted per tick (0 = Nc);
                                  # over-budget clients retry next tick
    net_hist_buckets: int = 64    # transit-time histogram resolution (§6)
    k_retry: int = 0              # max retry respawns per Disruption tick
                                  # (0 = auto: min(C, max(256, C/8)));
                                  # over-budget failures fail permanently —
                                  # a per-tick retry admission budget (§7)

    def validate(self) -> None:
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            lo = 0 if f.name in ("k_fire", "k_retry") else 1
            if not isinstance(v, int) or v < lo:
                raise ValueError(
                    f"SimCaps.{f.name} must be an int ≥ {lo}, got {v!r}")


@dataclasses.dataclass(frozen=True)
class SimParams:
    """Static scalar parameters of a simulation run (closed over in jit)."""

    # --- time -----------------------------------------------------------
    dt: float = 0.1               # seconds per tick
    n_ticks: int = 1000

    # --- request generator (paper Alg 1) --------------------------------
    n_clients: int = 100          # N_c, final number of clients
    spawn_rate: float = 1.0       # v, clients per second
    wait_lo: float = 5.0          # p0 (seconds)
    wait_hi: float = 15.0         # p1 (seconds)
    num_limit: int = 2 ** 31 - 1  # numLimit (max generated requests)

    # --- scheduling (paper §4.2) ----------------------------------------
    lb_policy: int = 0            # policies.LB_* (round-robin default)
    share_policy: int = 0         # policies.SHARE_* (equal time slice)
    max_concurrent: int = 0       # 0 = pure time sharing (unbounded)
    net_latency_s: float = 0.0    # per-RPC-hop network latency (seconds)

    # --- network fabric (DESIGN.md §6) -----------------------------------
    network: str = "uniform"      # "uniform": load-independent net_latency_s
                                  # per hop (the legacy degenerate mode);
                                  # "fabric": payloads transit host NICs with
                                  # max-min fair bandwidth contention
    nic_egress_mbps: float = 1000.0   # per-host NIC egress capacity
    nic_ingress_mbps: float = 1000.0  # per-host NIC ingress capacity
    waterfill_iters: int = 2      # water-filling freeze rounds (static:
                                  # exact max-min for ≤ this many bottleneck
                                  # levels, conservative — never
                                  # oversubscribing — beyond; raise for
                                  # deep multi-bottleneck fabrics)
    net_hist_bin_s: float = 0.01  # transit-time histogram bin width (s)

    # --- scaling (paper §5.3) -------------------------------------------
    scaling_policy: int = 0       # policies.SCALE_* (NS default)
    scale_interval: int = 50      # ticks between scaling events
    hs_util_hi: float = 0.8       # HS scale-out threshold (service avg util)
    hs_util_lo: float = 0.2       # HS scale-in threshold
    vs_util_hi: float = 0.8       # VS scale-up threshold (instance util)
    vs_util_lo: float = 0.2
    vs_up_factor: float = 1.5
    vs_down_factor: float = 0.75
    util_ema: float = 0.2         # EMA coefficient for utilization signal

    # --- migration (paper §5.1) -----------------------------------------
    migration_enabled: bool = False
    mig_vm_util_hi: float = 0.9

    # --- fault injection & resilience (DESIGN.md §7) ---------------------
    faults: str = "none"          # "none": the fault-free engine (exact
                                  # pre-faults program, bit-pinned);
                                  # "chaos": Disruption tick phase — host
                                  # crash/recovery, instance kills, NIC
                                  # degradation, retries, circuit breakers
    host_mtbf_s: float = float("inf")   # mean time between host crashes
    host_mttr_s: float = 30.0           # mean host recovery time
    inst_kill_rate: float = 0.0         # instance kills per second per pod
    inst_mttr_s: float = 15.0           # mean pod restart time (host up)
    nic_degrade_rate: float = 0.0       # NIC degradations per second per host
    nic_mttr_s: float = 30.0            # mean NIC recovery time
    nic_degrade_factor: float = 1.0     # capacity multiplier while degraded
    retry_budget: int = 2         # default retries per RPC (per-edge
                                  # overrides via the registry "retries" key)
    retry_timeout_s: float = float("inf")  # per-attempt timeout (age of the
                                  # attempt before it counts as failed)
    cb_err_thresh: float = 2.0    # breaker trip threshold on the per-edge
                                  # error-rate EMA (> 1 = breaker disabled)
    cb_alpha: float = 0.3         # error-rate EMA coefficient
    cb_cooldown_s: float = 10.0   # open → half-open cooldown
    egress_shaping: bool = False  # clamp per-instance Transit egress by
                                  # Instances.bw (fabric mode, §6)

    # --- gray failure (fail-slow / blast radius, DESIGN.md §7.1) ---------
    host_slow_mtbf_s: float = float("inf")  # mean time between fail-slow
                                  # episodes per host (inf = never)
    host_slow_mttr_s: float = 30.0          # mean fail-slow episode length
    host_slow_factor: float = 0.25          # MIPS multiplier while slow
    nic_degrade_spread: float = 0.0         # NIC brownout severity spread:
                                  # each degradation samples its factor from
                                  # U[factor − spread, factor + spread]∩[0,1]
    zone_fault_rate: float = 0.0  # zone crash draws per second per zone —
                                  # one draw downs EVERY host of the zone
                                  # (hosts recover individually, host_mttr_s)
    zone_slow_rate: float = 0.0   # zone fail-slow draws per second per zone
    zone_partition_rate: float = 0.0   # partial-partition draws per second
                                  # per zone PAIR (cuts their link capacity)
    zone_partition_mttr_s: float = 30.0  # mean partition length
    eject_err_thresh: float = 2.0 # outlier-ejection trip threshold on the
                                  # per-replica error EMA (> 1 = disabled)
    eject_lat_factor: float = 0.0 # latency outlier trip: replica latency
                                  # EMA > factor × its service's mean
                                  # (0 = latency ejection disabled)
    eject_cooldown_s: float = 10.0  # ejected → probe (half-open) cooldown

    # --- usage accounting (paper §5.2 linear model) ----------------------
    idle_mips_frac: float = 0.0   # idle floor: instances consume a small
                                  # fraction of their allocation when ON
    vs_overhead_frac: float = 0.0 # resize churn: vertically-scaled
                                  # instances pay a usage surcharge

    # --- observability (DESIGN.md §9) ------------------------------------
    telemetry: str = "none"       # "none": zero telemetry state, program
                                  # bit-identical to the pre-obs engine;
                                  # "stream": per-window metric rows ring
                                  # out through a double-buffered
                                  # io_callback tap + sampled span tracing
    tel_window_ticks: int = 16    # ticks per metric-row window
    tel_windows: int = 8          # metric ring capacity W (even; one
                                  # io_callback flush per W/2 windows)
    tel_span_k: int = 100         # trace 1 request in k (seeded Bernoulli)
    tel_span_cap: int = 1024      # span ring capacity (overflow drops
                                  # are counted exactly, never overwrite)
    tel_span_tick_cap: int = 0    # per-tick span staging budget (0 = the
                                  # ring capacity; sampled finishers past
                                  # it drop — counted, never silent)
    tel_tag: float = 0.0          # row tag (traced; run_batch auto-tags
                                  # sweep points when left at 0)

    # --- SLO objectives & burn-rate alerting (DESIGN.md §10) -------------
    alerting: str = "none"        # "none": no alert state, program
                                  # bit-identical to the alert-free engine;
                                  # "burn": Alerting tick stage — per-service
                                  # multi-window burn-rate rules + alert
                                  # state machine (requires telemetry="stream")
    hs_mode: str = "util"         # horizontal scale-out gate: "util"
                                  # (threshold on the utilization EMA) or
                                  # "slo_burn" (firing burn alerts + a
                                  # stabilization window); TRACED — sweep
                                  # points select per-point, no recompile
    slo_budget: float = 0.0       # run-wide error-budget fraction (allowed
                                  # share of slow completions per service);
                                  # 0 disables every objective without a
                                  # per-service override (traced)
    slo_fast_burn: float = 14.4   # fast-rule burn threshold (Google SRE
                                  # page rule: 14.4× budget burn; traced)
    slo_slow_burn: float = 6.0    # slow-rule burn threshold (traced)
    slo_short_wins: int = 3       # short lookback, in CLOSED telemetry
                                  # windows (static: sizes the rule masks)
    slo_long_wins: int = 12       # long lookback = SLI ring length (static)
    slo_for_ticks: int = 5        # hysteresis: rule must hold this many
                                  # consecutive ticks before pending→firing
    slo_stabilize_s: float = 30.0 # burn-mode scale-out stabilization window
                                  # per service (traced)
    slo_eject_tighten: float = 1.0  # outlier-ejection threshold multiplier
                                  # applied while a latency alert fires on
                                  # the replica's service (traced; 1 = off)
    slo_event_cap: int = 256      # alert-transition ring capacity (overflow
                                  # drops are counted exactly)

    # --- backend ---------------------------------------------------------
    use_pallas_tick: bool = False # fused cloudlet_step TPU kernel for the
                                  # execution phase (CPU runs the jnp ref)
    pallas_interpret: bool = False  # force the Pallas kernel in interpret
                                  # mode (CPU validation / perf tracking)

    # --- QoS -------------------------------------------------------------
    slo_ms: float = 1000.0        # SLO threshold on response time (ms)
    mi_per_milicore: float = 0.001  # milicores = used_mips / mi_per_milicore

    seed: int = 0


# Horizontal scale-out gates (dyn.hs_mode encodes the index; traced so one
# run_batch sweep compares control planes without recompiling).
HS_MODES = ("util", "slo_burn")

# Burn-rate rules evaluated per service (axis 1 of AlertState.astate) and
# the alert state machine's states. Names are the exported label values.
ALERT_RULES = ("SLOFastBurn", "SLOSlowBurn")
ALERT_STATES = ("inactive", "pending", "firing", "resolved")
ALERT_INACTIVE, ALERT_PENDING, ALERT_FIRING, ALERT_RESOLVED = 0, 1, 2, 3


class DynParams(NamedTuple):
    """Traced scalar parameters — passed as a jit *argument* so sweeping
    loads/thresholds (benchmarks, calibration) never recompiles the tick.

    Static knobs that change the program structure (policy selectors,
    pool sizes, n_ticks) stay in SimParams/SimCaps and are closed over.
    """

    dt: jnp.ndarray
    n_clients: jnp.ndarray
    spawn_rate: jnp.ndarray
    wait_lo: jnp.ndarray
    wait_hi: jnp.ndarray
    num_limit: jnp.ndarray
    max_concurrent: jnp.ndarray
    scale_interval: jnp.ndarray
    hs_util_hi: jnp.ndarray
    hs_util_lo: jnp.ndarray
    vs_util_hi: jnp.ndarray
    vs_util_lo: jnp.ndarray
    vs_up_factor: jnp.ndarray
    vs_down_factor: jnp.ndarray
    util_ema: jnp.ndarray
    mig_vm_util_hi: jnp.ndarray
    slo_ms: jnp.ndarray
    net_latency: jnp.ndarray
    idle_mips_frac: jnp.ndarray
    vs_overhead_frac: jnp.ndarray
    nic_egress_mbps: jnp.ndarray
    nic_ingress_mbps: jnp.ndarray
    host_mtbf_s: jnp.ndarray
    host_mttr_s: jnp.ndarray
    inst_kill_rate: jnp.ndarray
    inst_mttr_s: jnp.ndarray
    nic_degrade_rate: jnp.ndarray
    nic_mttr_s: jnp.ndarray
    nic_degrade_factor: jnp.ndarray
    retry_budget: jnp.ndarray
    retry_timeout_s: jnp.ndarray
    cb_err_thresh: jnp.ndarray
    cb_alpha: jnp.ndarray
    cb_cooldown_s: jnp.ndarray
    host_slow_mtbf_s: jnp.ndarray
    host_slow_mttr_s: jnp.ndarray
    host_slow_factor: jnp.ndarray
    nic_degrade_spread: jnp.ndarray
    zone_fault_rate: jnp.ndarray
    zone_slow_rate: jnp.ndarray
    zone_partition_rate: jnp.ndarray
    zone_partition_mttr_s: jnp.ndarray
    eject_err_thresh: jnp.ndarray
    eject_lat_factor: jnp.ndarray
    eject_cooldown_s: jnp.ndarray
    hs_mode: jnp.ndarray
    slo_budget: jnp.ndarray
    slo_fast_burn: jnp.ndarray
    slo_slow_burn: jnp.ndarray
    slo_stabilize_s: jnp.ndarray
    slo_eject_tighten: jnp.ndarray
    tel_tag: jnp.ndarray

    @staticmethod
    def from_params(p: "SimParams") -> "DynParams":
        f = lambda v: jnp.asarray(v, jnp.float32)
        i = lambda v: jnp.asarray(v, jnp.int32)
        return DynParams(
            dt=f(p.dt), n_clients=i(p.n_clients), spawn_rate=f(p.spawn_rate),
            wait_lo=f(p.wait_lo), wait_hi=f(p.wait_hi),
            num_limit=i(p.num_limit), max_concurrent=i(p.max_concurrent),
            scale_interval=i(p.scale_interval),
            hs_util_hi=f(p.hs_util_hi), hs_util_lo=f(p.hs_util_lo),
            vs_util_hi=f(p.vs_util_hi), vs_util_lo=f(p.vs_util_lo),
            vs_up_factor=f(p.vs_up_factor), vs_down_factor=f(p.vs_down_factor),
            util_ema=f(p.util_ema), mig_vm_util_hi=f(p.mig_vm_util_hi),
            slo_ms=f(p.slo_ms), net_latency=f(p.net_latency_s),
            idle_mips_frac=f(p.idle_mips_frac),
            vs_overhead_frac=f(p.vs_overhead_frac),
            nic_egress_mbps=f(p.nic_egress_mbps),
            nic_ingress_mbps=f(p.nic_ingress_mbps),
            host_mtbf_s=f(p.host_mtbf_s), host_mttr_s=f(p.host_mttr_s),
            inst_kill_rate=f(p.inst_kill_rate), inst_mttr_s=f(p.inst_mttr_s),
            nic_degrade_rate=f(p.nic_degrade_rate),
            nic_mttr_s=f(p.nic_mttr_s),
            nic_degrade_factor=f(p.nic_degrade_factor),
            retry_budget=i(p.retry_budget),
            retry_timeout_s=f(p.retry_timeout_s),
            cb_err_thresh=f(p.cb_err_thresh), cb_alpha=f(p.cb_alpha),
            cb_cooldown_s=f(p.cb_cooldown_s),
            host_slow_mtbf_s=f(p.host_slow_mtbf_s),
            host_slow_mttr_s=f(p.host_slow_mttr_s),
            host_slow_factor=f(p.host_slow_factor),
            nic_degrade_spread=f(p.nic_degrade_spread),
            zone_fault_rate=f(p.zone_fault_rate),
            zone_slow_rate=f(p.zone_slow_rate),
            zone_partition_rate=f(p.zone_partition_rate),
            zone_partition_mttr_s=f(p.zone_partition_mttr_s),
            eject_err_thresh=f(p.eject_err_thresh),
            eject_lat_factor=f(p.eject_lat_factor),
            eject_cooldown_s=f(p.eject_cooldown_s),
            hs_mode=i(HS_MODES.index(p.hs_mode)),
            slo_budget=f(p.slo_budget),
            slo_fast_burn=f(p.slo_fast_burn),
            slo_slow_burn=f(p.slo_slow_burn),
            slo_stabilize_s=f(p.slo_stabilize_s),
            slo_eject_tighten=f(p.slo_eject_tighten),
            tel_tag=f(p.tel_tag))


class Clients(NamedTuple):
    """Locust-style closed-loop client pool (paper Alg 1)."""

    wait: jnp.ndarray        # [Nc] i32 ticks until next request (0 = fire)


class Requests(NamedTuple):
    """Append-only request pool (paper §4.3)."""

    count: jnp.ndarray        # scalar i32, number of allocated requests
    api: jnp.ndarray          # [R] i32
    arrival: jnp.ndarray      # [R] f32 seconds
    outstanding: jnp.ndarray  # [R] i32 cloudlets in flight
    spawned: jnp.ndarray      # [R] i32 total cloudlets ever spawned
    finish: jnp.ndarray       # [R] f32 max cloudlet finish time so far
    response: jnp.ndarray     # [R] f32 final response (s), -1 while open
    critical_len: jnp.ndarray # [R] i32 nodes on the critical (longest) chain
    failed: jnp.ndarray       # [R] u8 1 = a cloudlet of this request failed
    #                           permanently (retries exhausted / fail-fast);
    #                           the request completes as a failed completion.
    #                           A Disruption-phase column: shape [0] in
    #                           faults="none" mode (mode-keyed registry) —
    #                           uint8 so even chaos runs pay one byte per
    #                           request on the scan carry, not a word


# --------------------------------------------------------------------------
# Mode-keyed pool column registry (DESIGN.md §2.2).
#
# The stacked cloudlet pool stores all i32 fields as one [C, NI] array and
# all f32 fields as one [C, NF] array, so spawning writes the whole pool
# with TWO row scatters instead of one scatter per field.  WHICH columns
# exist is mode-dependent: each tick phase declares the columns it needs,
# and `resolve_layout` unions the declarations of the phases a SimParams
# actually enables into a static `PoolLayout`.  A default
# network="uniform"/faults="none" run therefore carries only the core
# columns — the fabric (src_host/rem_bytes) and resilience
# (attempt/edge/src_inst) columns never ride the scan carry unless their
# phase is compiled in.
# --------------------------------------------------------------------------

# Full column vocabulary, in storage order: (name, block, init value).
# The init value is what a free slot holds (`zeros_state`) — spawn waves
# always initialize whole rows, so only free slots ever show it.
POOL_COLUMNS = (
    ("status", "i", 0),        # CL_*
    ("req", "i", -1),          # owning request
    ("service", "i", -1),      # service node
    ("inst", "i", -1),         # assigned instance (-1 = unassigned)
    ("wait_ticks", "i", 0),    # ticks spent in the waiting queue
    ("depth", "i", 0),         # hops from the root cloudlet
    ("src_host", "i", -1),     # transfer source host (-1 = client / none)
    ("attempt", "i", 0),       # retry attempt counter (0 = first try, §7)
    ("edge", "i", -1),         # service-graph edge this RPC traverses:
    #                            parent_svc * d_max + slot for call edges,
    #                            S * d_max + api for client→entry edges
    #                            (retry policy / circuit breaker key, §7)
    ("src_inst", "i", -1),     # caller instance (-1 = external client)
    ("length", "f", 0.0),      # total MI (Gaussian, paper §4.1.2)
    ("rem", "f", 0.0),         # remaining MI
    ("arrival", "f", 0.0),     # seconds (of the current attempt)
    ("start", "f", -1.0),      # first-execution time (-1 = not yet)
    ("rem_bytes", "f", 0.0),   # MB still in flight (TRANSIT status, §6)
)
CL_I_FIELDS = tuple(n for n, b, _ in POOL_COLUMNS if b == "i")
CL_F_FIELDS = tuple(n for n, b, _ in POOL_COLUMNS if b == "f")
_COL_BLOCK = {n: b for n, b, _ in POOL_COLUMNS}
_COL_INIT = {n: v for n, _, v in POOL_COLUMNS}

# Declared per-column invariant bounds, keyed like POOL_COLUMNS.  These are
# the *inductive* invariants the index-safety verifier (analysis/intervals.py,
# DESIGN.md §8) seeds the tick jaxpr with and re-checks on the tick's output
# state: every value a column can hold at a tick boundary lies in
# ``fn(caps, app) -> (lo, hi)``.  Id-like columns are what make pool
# gathers/scatters provable; unbounded counters use ``inf``.
_INF = float("inf")
POOL_COLUMN_BOUNDS = {
    "status":     lambda caps, app: (CL_FREE, CL_TRANSIT),
    "req":        lambda caps, app: (-1, caps.max_requests - 1),
    "service":    lambda caps, app: (-1, app.n_services - 1),
    "inst":       lambda caps, app: (-1, caps.max_instances - 1),
    "wait_ticks": lambda caps, app: (0, _INF),
    # acyclicity (validate_app) caps any call chain at n_services hops
    "depth":      lambda caps, app: (0, max(app.n_services - 1, 0)),
    "src_host":   lambda caps, app: (-1, app.n_hosts - 1),
    "attempt":    lambda caps, app: (0, _INF),
    "edge":       lambda caps, app: (
        -1, edge_table_size(app.n_services, caps.d_max, app.n_apis) - 1),
    "src_inst":   lambda caps, app: (-1, caps.max_instances - 1),
    "length":     lambda caps, app: (0.0, _INF),
    "rem":        lambda caps, app: (-_INF, _INF),
    "arrival":    lambda caps, app: (0.0, _INF),
    "start":      lambda caps, app: (-1.0, _INF),
    "rem_bytes":  lambda caps, app: (-_INF, _INF),
}

# Tick phase → columns it reads/writes (the registry the layout is keyed
# on).  The first four phases exist in every mode; Transit only under
# network="fabric", Disruption only under faults="chaos", and the
# egress-shaping clamp (a Transit sub-feature) only when opted in.
PHASE_COLUMNS = {
    "Generation": ("status", "req", "service", "inst", "wait_ticks",
                   "depth", "length", "rem", "arrival", "start"),
    "Dispatch":   ("status", "service", "inst", "wait_ticks", "arrival",
                   "start"),
    "Execute":    ("status", "req", "service", "inst", "depth", "rem",
                   "arrival", "start"),
    # Chaos-mode Execute additionally folds per-edge success counts for
    # the breaker EMA off cl.edge — drift simcheck's layout-access
    # checker caught (the column was only declared under Disruption; the
    # resolved layout is unchanged, the *attribution* was wrong).
    "Execute/chaos": ("edge",),
    "Derive":     ("status", "req", "service", "inst", "depth", "length",
                   "rem", "arrival", "start"),
    "Transit":    ("status", "inst", "arrival", "src_host", "rem_bytes"),
    "Transit/egress_shaping": ("src_inst",),
    "Disruption": ("status", "req", "service", "inst", "depth", "attempt",
                   "edge", "src_inst", "length", "rem", "arrival", "start"),
    # Fabric-mode retry respawns re-derive the retried hop's source host
    # (same checker catch as Execute/chaos: the column was riding on
    # Transit's declaration; resolved layouts are unchanged).
    "Disruption/fabric": ("src_host",),
    # Telemetry (telemetry="stream", DESIGN.md §9) reads finished rows
    # into the span ring and samples end-of-tick gauges; it only ever
    # RE-reads columns other phases already pulled into the layout, so
    # every resolved layout is unchanged and telemetry="none" stays
    # bit-identical by construction.
    "Telemetry": ("status", "req", "service", "wait_ticks", "arrival",
                  "start"),
    "Telemetry/chaos": ("edge", "attempt"),
    "Telemetry/fabric": ("src_host", "rem_bytes"),
    # Alerting (alerting="burn", DESIGN.md §10) folds finished-hop sojourn
    # times into the per-service SLI accumulators; like Telemetry it is
    # observation-only — `arrival` rides on Execute's declaration, so no
    # resolved layout grows.
    "Alerting": ("arrival",),
}


@dataclasses.dataclass(frozen=True)
class PoolLayout:
    """Static name → column-index map of the stacked cloudlet pool.

    Resolved once per mode combination (`resolve_layout`) and carried as
    pytree *aux data* on :class:`Cloudlets`, so it is hashable, closed
    over in jit, and keys the compile cache together with the structural
    SimParams knobs that produced it.
    """

    i_fields: Tuple[str, ...]
    f_fields: Tuple[str, ...]

    def i(self, name: str) -> int:
        """Index of an i32 column in the [C, NI] block."""
        try:
            return self.i_fields.index(name)
        except ValueError:
            raise KeyError(
                f"pool column {name!r} is not part of this mode's layout "
                f"(i32 columns: {self.i_fields})") from None

    def f(self, name: str) -> int:
        """Index of an f32 column in the [C, NF] block."""
        try:
            return self.f_fields.index(name)
        except ValueError:
            raise KeyError(
                f"pool column {name!r} is not part of this mode's layout "
                f"(f32 columns: {self.f_fields})") from None

    def __contains__(self, name: str) -> bool:
        return name in self.i_fields or name in self.f_fields

    @property
    def columns(self) -> Tuple[str, ...]:
        return self.i_fields + self.f_fields

    def init_ints(self) -> np.ndarray:
        return np.array([_COL_INIT[n] for n in self.i_fields], np.int32)

    def init_flts(self) -> np.ndarray:
        return np.array([_COL_INIT[n] for n in self.f_fields], np.float32)


@functools.lru_cache(maxsize=None)
def _layout_for(network: str, faults: str, egress_shaping: bool,
                telemetry: bool = False, alerting: bool = False) -> PoolLayout:
    phases = ["Generation", "Dispatch", "Execute", "Derive"]
    if faults == "chaos":
        phases.append("Disruption")
        phases.append("Execute/chaos")
    if network == "fabric":
        phases.append("Transit")
        if faults == "chaos":
            phases.append("Disruption/fabric")
        if egress_shaping:
            phases.append("Transit/egress_shaping")
    if telemetry:
        # observation-only: the Telemetry declarations are a subset of the
        # union above in every mode, so the resolved layout never grows
        phases.append("Telemetry")
        if faults == "chaos":
            phases.append("Telemetry/chaos")
        if network == "fabric":
            phases.append("Telemetry/fabric")
    if alerting:
        phases.append("Alerting")
    need = set()
    for p in phases:
        cols = set(PHASE_COLUMNS[p])
        if p.startswith("Telemetry") or p == "Alerting":
            extra = cols - need
            if extra:
                raise ValueError(
                    f"PHASE_COLUMNS[{p!r}] declares column(s) "
                    f"{sorted(extra)} that no simulating phase carries in "
                    "this mode — telemetry/alerting is observation-only "
                    "and must not grow the pool layout")
        need |= cols
    return PoolLayout(
        i_fields=tuple(n for n in CL_I_FIELDS if n in need),
        f_fields=tuple(n for n in CL_F_FIELDS if n in need))


def resolve_layout(params: "SimParams") -> PoolLayout:
    """The static pool layout a SimParams' enabled phases require."""
    return _layout_for(params.network, params.faults,
                       params.network == "fabric" and params.egress_shaping,
                       params.telemetry == "stream",
                       params.telemetry == "stream"
                       and params.alerting == "burn")


FULL_LAYOUT = _layout_for("fabric", "chaos", True)   # every column


@jax.tree_util.register_pytree_node_class
class Cloudlets:
    """Active-set RpcCloudlet buffer (paper §4.1.2, §4.2), stored as two
    stacked column blocks so one spawn wave is two scatters.

    The column set is the mode-keyed :class:`PoolLayout` (aux data, not a
    leaf): named accessors (``cl.status`` …) and the column writers
    (`with_cols`, `pool.scatter_pool`) resolve indices through it, so no
    caller hard-codes a position and absent columns cost nothing.
    Writers accept any registered column name and silently skip columns
    outside the active layout — spawn sites stay mode-agnostic; reading
    an absent column raises ``KeyError`` (reads are always mode-gated).
    """

    __slots__ = ("ints", "flts", "layout")

    def __init__(self, ints: jnp.ndarray, flts: jnp.ndarray,
                 layout: PoolLayout = FULL_LAYOUT):
        self.ints = ints        # [C, len(layout.i_fields)] i32
        self.flts = flts        # [C, len(layout.f_fields)] f32
        self.layout = layout

    # --- pytree protocol (layout is static aux data) -------------------
    def tree_flatten(self):
        return (self.ints, self.flts), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], children[1], layout)

    def replace(self, ints=None, flts=None) -> "Cloudlets":
        return Cloudlets(self.ints if ints is None else ints,
                         self.flts if flts is None else flts, self.layout)

    # --- named column views --------------------------------------------
    def col(self, name: str) -> jnp.ndarray:
        if _COL_BLOCK.get(name) == "i":
            return self.ints[:, self.layout.i(name)]
        if _COL_BLOCK.get(name) == "f":
            return self.flts[:, self.layout.f(name)]
        raise KeyError(f"unknown pool column {name!r}")

    @property
    def status(self) -> jnp.ndarray:
        return self.col("status")

    @property
    def req(self) -> jnp.ndarray:
        return self.col("req")

    @property
    def service(self) -> jnp.ndarray:
        return self.col("service")

    @property
    def inst(self) -> jnp.ndarray:
        return self.col("inst")

    @property
    def wait_ticks(self) -> jnp.ndarray:
        return self.col("wait_ticks")

    @property
    def depth(self) -> jnp.ndarray:
        return self.col("depth")

    @property
    def src_host(self) -> jnp.ndarray:
        return self.col("src_host")

    @property
    def attempt(self) -> jnp.ndarray:
        return self.col("attempt")

    @property
    def edge(self) -> jnp.ndarray:
        return self.col("edge")

    @property
    def src_inst(self) -> jnp.ndarray:
        return self.col("src_inst")

    @property
    def length(self) -> jnp.ndarray:
        return self.col("length")

    @property
    def rem(self) -> jnp.ndarray:
        return self.col("rem")

    @property
    def arrival(self) -> jnp.ndarray:
        return self.col("arrival")

    @property
    def start(self) -> jnp.ndarray:
        return self.col("start")

    @property
    def rem_bytes(self) -> jnp.ndarray:
        return self.col("rem_bytes")

    def with_cols(self, **cols) -> "Cloudlets":
        """Replace whole [C] field columns by name (dispatch/execute path);
        consecutive column writes fuse into one pass under jit.  Registered
        columns outside the active layout are skipped (mode-agnostic
        callers); unregistered names raise."""
        ints, flts = self.ints, self.flts
        L = self.layout
        for name, v in cols.items():
            if name not in _COL_BLOCK:
                raise TypeError(f"unknown pool column {name!r}")
            if name not in L:
                continue
            if _COL_BLOCK[name] == "i":
                ints = ints.at[:, L.i(name)].set(jnp.asarray(v, ints.dtype))
            else:
                flts = flts.at[:, L.f(name)].set(jnp.asarray(v, flts.dtype))
        return Cloudlets(ints, flts, L)


class Instances(NamedTuple):
    """Instance pool (pods/containers; paper §3.3)."""

    status: jnp.ndarray      # [I] i32 INST_*
    service: jnp.ndarray     # [I] i32 (-1 on free slots)
    vm: jnp.ndarray          # [I] i32
    host: jnp.ndarray        # [I] i32 physical host (NIC attachment, §6);
    #                          co-located with the VM, moves on migration
    mips: jnp.ndarray        # [I] f32 current CPU allocation (MI/s)
    limit_mips: jnp.ndarray  # [I] f32 vertical-scaling cap ("limits.share")
    request_mips: jnp.ndarray# [I] f32 baseline request ("requests.share")
    ram: jnp.ndarray         # [I] f32 current RAM allocation (MB)
    limit_ram: jnp.ndarray   # [I] f32
    bw: jnp.ndarray          # [I] f32 bandwidth (Mbps)
    n_exec: jnp.ndarray      # [I] i32 executing cloudlets this tick
    used_mips: jnp.ndarray   # [I] f32 consumed this tick
    used_ram: jnp.ndarray    # [I] f32 linear cloudlet→RAM model (paper §5.2)
    used_bw: jnp.ndarray     # [I] f32 linear spawn→BW model
    util_ema: jnp.ndarray    # [I] f32 smoothed utilization (scaling signal)
    usage_sum: jnp.ndarray   # [I] f32 ∫ used_mips dt  (usage history)
    busy_ticks: jnp.ndarray  # [I] i32 ticks with n_exec > 0


class VMs(NamedTuple):
    mips: jnp.ndarray        # [V] f32 capacity
    mips_used: jnp.ndarray   # [V] f32 allocated to instances
    ram: jnp.ndarray         # [V] f32
    ram_used: jnp.ndarray    # [V] f32


class Hosts(NamedTuple):
    """Per-host hardware description (fabric §6, heterogeneity §7.1).

    One host per VM slot (host id = vm id).  Effective port capacity is
    ``scale * dyn.nic_{egress,ingress}_mbps`` so heterogeneous clusters keep
    their shape while sweeps scale the whole fabric through one traced
    scalar.  ``cpu_scale`` is the CPU analogue: instances execute at
    ``cpu_scale[host] ×`` their allocated MIPS, so a slow hardware class
    (old CPUs, throttled nodes) degrades *speed* while the placement
    bin-packing still sees the full requested milicores — the
    resource-model asymmetry real schedulers suffer (default 1.0
    everywhere, which multiplies out exactly).
    """

    egress_scale: jnp.ndarray    # [H] f32 NIC egress capacity multiplier
    ingress_scale: jnp.ndarray   # [H] f32 NIC ingress capacity multiplier
    cpu_scale: jnp.ndarray       # [H] f32 execution-rate multiplier


class NetStats(NamedTuple):
    """Network-fabric usage history (bytes moved, link utilization,
    transit-time distribution) — all zeros in ``network="uniform"`` mode."""

    bytes_out: jnp.ndarray     # [H] f32 MB egressed per host
    bytes_in: jnp.ndarray      # [H] f32 MB ingressed per host
    egress_busy: jnp.ndarray   # [H] f32 ∫ egress utilization dt (seconds)
    ingress_busy: jnp.ndarray  # [H] f32 ∫ ingress utilization dt
    transits: jnp.ndarray      # scalar i32 completed transfers
    transit_sum: jnp.ndarray   # scalar f32 Σ transit durations (s)
    hist: jnp.ndarray          # [NB] i32 transit-time histogram
    #                            (bin = net_hist_bin_s; last bin = overflow)


class FaultState(NamedTuple):
    """Fault-injection & resilience state (Disruption phase, DESIGN.md §7).

    ``host_up`` / ``nic_ok`` are [H] in every mode (placement and scaling
    read them unconditionally); every other table is a chaos-only column —
    zero-width in ``faults="none"`` mode so the fault-free scan carry pays
    nothing for the resilience machinery.

    The circuit breaker per service edge is a pure status mask over
    ``edge_open_until``: CLOSED while ``open_until <= 0``, OPEN while
    ``time < open_until`` (new calls fail fast), HALF-OPEN once the cooldown
    passes (``0 < open_until <= time`` — probe traffic flows; the first
    observed failure re-opens, the first all-success tick closes).
    Outlier ejection (``inst_eject_until``) mirrors the same three states
    per replica: an OPEN replica is compacted out of the dispatch rank
    table (`policies.eject_view`), a HALF-OPEN one receives probe traffic.
    """

    host_up: jnp.ndarray         # [H] i32 1 = host up
    nic_ok: jnp.ndarray          # [H] i32 1 = NIC healthy (degradation)
    edge_open_until: jnp.ndarray # [E] f32 breaker clock (see above)
    edge_err_ema: jnp.ndarray    # [E] f32 error-rate EMA per edge
    edge_succ: jnp.ndarray       # [E] i32 successes since the last breaker
    #                              update (written by execute, consumed and
    #                              reset by the next Disruption phase)
    host_slow: jnp.ndarray       # [H] i32 1 = fail-slow episode active
    #                              (Execute degrades MIPS by host_slow_factor)
    nic_factor: jnp.ndarray      # [H] f32 NIC capacity multiplier Transit
    #                              applies (1.0 healthy; sampled per brownout
    #                              from the severity distribution)
    zone_cut: jnp.ndarray        # [H, H] i32 symmetric zone-pair partition
    #                              mask (zone ids index it; Z ≤ H so the
    #                              host count bounds the table)
    inst_err_ema: jnp.ndarray    # [I] f32 per-replica error-rate EMA
    inst_lat_ema: jnp.ndarray    # [I] f32 per-replica mean-sojourn EMA (s)
    inst_eject_until: jnp.ndarray# [I] f32 ejection clock (breaker states)
    inst_succ: jnp.ndarray       # [I] i32 successes since the last ejection
    #                              update (execute-written, like edge_succ)
    inst_lat_sum: jnp.ndarray    # [I] f32 Σ sojourn of those successes


class FaultStats(NamedTuple):
    """Cumulative resilience/availability history (joins QoSReport, §7)."""

    host_crashes: jnp.ndarray    # i32 injected host-down events
    host_recoveries: jnp.ndarray # i32 host recoveries (observed-MTTR denom.)
    inst_kills: jnp.ndarray      # i32 injected instance kills
    failed_attempts: jnp.ndarray # i32 cloudlet attempts that failed
    retries: jnp.ndarray         # i32 retry attempts respawned
    failfast: jnp.ndarray        # i32 attempts failed fast by an open breaker
    failed_requests: jnp.ndarray # i32 requests completed as failed
    breaker_trips: jnp.ndarray   # i32 closed → open transitions
    down_time_s: jnp.ndarray     # f32 Σ host-down seconds (MTTR numerator)
    ejections: jnp.ndarray       # i32 replica outlier ejections
    readmissions: jnp.ndarray    # i32 ejected replicas re-admitted clean
    zone_faults: jnp.ndarray     # i32 zone-correlated crash/slow draws fired
    partitions: jnp.ndarray      # i32 zone-pair partitions opened
    slow_episodes: jnp.ndarray   # i32 host fail-slow episodes started
    slow_time_s: jnp.ndarray     # f32 Σ host-slow seconds


# --------------------------------------------------------------------------
# Telemetry schemas (DESIGN.md §9).  Declared here, next to POOL_COLUMNS,
# because zeros_state sizes the TelemetryState buffers off them; the
# host-side renderers (repro/obs) re-export these tuples.
# --------------------------------------------------------------------------

# One metric row per closed window, in ring-storage order.
TEL_METRIC_COLUMNS = (
    "window",            # window index (monotone, 0-based)
    "time_s",            # sim time at window close
    "tag",               # sweep-point tag (dyn.tel_tag)
    "completed",         # requests completed in the window (sum)
    "generated",         # requests generated in the window (sum)
    "n_waiting",         # gauges sampled at window close ↓
    "n_exec",
    "n_transit",
    "used_mips",
    "active_instances",
    "net_mb_inflight",   # Σ rem_bytes in TRANSIT (fabric mode; else 0)
    "failed_attempts",   # cumulative FaultStats at close (0 faults off)
    "retries",           # cumulative FaultStats at close
    "spans",             # spans recorded so far (cumulative)
    "span_drops",        # spans dropped at ring capacity (cumulative)
)
# Window-summed accumulators (prefix of the row's sum section).
TEL_ACC_COLUMNS = ("completed", "generated")
# One span per sampled finished cloudlet (hop), split by block dtype.
TEL_SPAN_I_COLUMNS = ("req", "service", "inst", "host", "src_host",
                      "edge", "attempt", "wait_ticks")
TEL_SPAN_F_COLUMNS = ("arrival", "start", "finish")


class TelemetryState(NamedTuple):
    """Device-side observability state (telemetry="stream", DESIGN.md §9).

    Mode-keyed like :class:`FaultState`: every buffer is zero-width under
    ``telemetry="none"`` so the default scan carry pays nothing.  The
    metric ring is double-buffered — ticks write rows into half the ring
    while the io_callback tap flushes the other, just-completed half.
    The span ring is append-until-full: overflow never overwrites, it
    increments the exact drop counter instead.
    """

    ring: jnp.ndarray        # [W, K] f32 metric rows (K = TEL_METRIC_…)
    acc: jnp.ndarray         # [len(TEL_ACC_COLUMNS)] f32 open-window sums
    win: jnp.ndarray         # [1] i32 windows closed so far
    span_i: jnp.ndarray      # [SP, NSI] i32 span ints
    span_f: jnp.ndarray      # [SP, NSF] f32 span timestamps
    span_n: jnp.ndarray      # [1] i32 spans recorded (≤ SP)
    span_drops: jnp.ndarray  # [1] i32 spans dropped at capacity
    sample: jnp.ndarray      # [R] u8 1 = request is traced (seeded 1-in-k)


def validate_telemetry(params: "SimParams") -> None:
    if params.telemetry not in ("none", "stream"):
        raise ValueError(
            f"SimParams.telemetry must be 'none' or 'stream', "
            f"got {params.telemetry!r}")
    if params.telemetry == "stream":
        if params.tel_windows < 2 or params.tel_windows % 2:
            raise ValueError(
                "SimParams.tel_windows must be an even int ≥ 2 (the ring "
                f"flushes in halves), got {params.tel_windows!r}")
        for f in ("tel_window_ticks", "tel_span_k", "tel_span_cap"):
            v = getattr(params, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"SimParams.{f} must be an int ≥ 1, got {v!r}")
        v = params.tel_span_tick_cap
        if not isinstance(v, int) or v < 0:
            raise ValueError(
                "SimParams.tel_span_tick_cap must be an int ≥ 0 "
                f"(0 = uncapped), got {v!r}")


class AlertState(NamedTuple):
    """Per-service SLO burn-rate alerting state (alerting="burn",
    DESIGN.md §10).

    Mode-keyed like :class:`TelemetryState`: every buffer is zero-width
    unless ``telemetry="stream"`` AND ``alerting="burn"``, so the default
    carry pays nothing and the sixth golden combo (alerting compiled in,
    objectives disabled) stays bit-identical by construction.  Axes:
    ``S`` services, ``NR = len(ALERT_RULES)`` burn rules, ``L`` closed SLI
    windows (``slo_long_wins``), ``AP`` event-ring rows (``slo_event_cap``).
    The transition ring is append-until-full with exact drop counting —
    the span-ring discipline.
    """

    sli_win: jnp.ndarray      # [L, S, 2] f32 closed windows of (good, bad)
    sli_acc: jnp.ndarray      # [S, 2] f32 open-window (good, bad) sums
    win: jnp.ndarray          # [1] i32 SLI windows closed so far
    astate: jnp.ndarray       # [S, NR] i32 ALERT_INACTIVE..ALERT_RESOLVED
    pending: jnp.ndarray      # [S, NR] i32 consecutive ticks condition held
    fires: jnp.ndarray        # [S, NR] i32 pending→firing transitions
    resolves: jnp.ndarray     # [S, NR] i32 firing→resolved transitions
    firing_ticks: jnp.ndarray # [S, NR] i32 ticks spent firing
    hold_until: jnp.ndarray   # [S] f32 burn-mode scale-out stabilization
    ev_time: jnp.ndarray      # [AP] f32 transition timestamps
    ev_service: jnp.ndarray   # [AP] i32
    ev_rule: jnp.ndarray      # [AP] i32 index into ALERT_RULES
    ev_state: jnp.ndarray     # [AP] i32 new state (index into ALERT_STATES)
    ev_n: jnp.ndarray         # [1] i32 transitions recorded (≤ AP)
    ev_drops: jnp.ndarray     # [1] i32 transitions dropped at capacity


def validate_alerting(params: "SimParams") -> None:
    if params.alerting not in ("none", "burn"):
        raise ValueError(
            f"SimParams.alerting must be 'none' or 'burn', "
            f"got {params.alerting!r}")
    if params.hs_mode not in HS_MODES:
        raise ValueError(
            f"SimParams.hs_mode must be one of {HS_MODES}, "
            f"got {params.hs_mode!r}")
    if params.alerting == "burn":
        if params.telemetry != "stream":
            raise ValueError(
                "alerting='burn' evaluates rules on the telemetry window "
                "cadence and requires telemetry='stream'")
        for f in ("slo_short_wins", "slo_long_wins", "slo_for_ticks",
                  "slo_event_cap"):
            v = getattr(params, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"SimParams.{f} must be an int ≥ 1, got {v!r}")
        if params.slo_long_wins < params.slo_short_wins:
            raise ValueError(
                "SimParams.slo_long_wins must be ≥ slo_short_wins "
                f"(got {params.slo_long_wins} < {params.slo_short_wins})")
        if not params.slo_eject_tighten > 0:
            raise ValueError(
                "SimParams.slo_eject_tighten must be > 0 (1 disables "
                f"tightening), got {params.slo_eject_tighten!r}")
    elif params.hs_mode == "slo_burn":
        raise ValueError(
            "hs_mode='slo_burn' gates scale-out on firing burn alerts and "
            "requires alerting='burn'")


class SchedState(NamedTuple):
    """Service→replica dispatch tables, maintained incrementally.

    ``inst_of_rank[s, r]`` is the instance slot of the r-th replica of
    service ``s`` (-1 beyond ``svc_replicas[s]``).  Placement fills it,
    HS scale-out/in mutates it, dispatch reads it every tick.
    """

    inst_of_rank: jnp.ndarray   # [S, R_max] i32
    svc_replicas: jnp.ndarray   # [S] i32


class SvcStats(NamedTuple):
    """Per-service usage history (paper §5.2) and node-delay estimates
    (feeds the critical-path analysis of §4.3.2)."""

    usage_sum: jnp.ndarray   # [S] f32 ∫ used_mips dt over replicas
    finished: jnp.ndarray    # [S] i32 cloudlets completed
    delay_sum: jnp.ndarray   # [S] f32 Σ (finish - arrival) sojourn
    exec_sum: jnp.ndarray    # [S] f32 Σ execution time
    wait_sum: jnp.ndarray    # [S] f32 Σ waiting time


class Counters(NamedTuple):
    spawned: jnp.ndarray         # i32 cloudlets ever created
    finished: jnp.ndarray        # i32 cloudlets ever finished
    dropped_cloudlets: jnp.ndarray
    dropped_requests: jnp.ndarray
    completed: jnp.ndarray       # i32 completed requests
    resp_sum: jnp.ndarray        # f32 Σ response
    slo_violations: jnp.ndarray  # i32
    migrations: jnp.ndarray      # i32
    scale_out: jnp.ndarray       # i32 HS scale-out events
    scale_in: jnp.ndarray        # i32 HS scale-in events
    scale_up: jnp.ndarray        # i32 VS scale-up events
    scale_down: jnp.ndarray      # i32 VS scale-down events
    branch_taken: jnp.ndarray    # i32 conditional calls drawn and made
    branch_skipped: jnp.ndarray  # i32 conditional calls drawn, not made


class SimState(NamedTuple):
    tick: jnp.ndarray       # i32
    time: jnp.ndarray       # f32 seconds
    rng: jnp.ndarray        # PRNG key
    rr: jnp.ndarray         # [S] i32 round-robin cursor per service
    clients: Clients
    requests: Requests
    cloudlets: Cloudlets
    instances: Instances
    vms: VMs
    hosts: Hosts
    net: NetStats
    sched: SchedState
    svc_stats: SvcStats
    counters: Counters
    fault: FaultState
    fstats: FaultStats
    telemetry: TelemetryState
    alerts: AlertState


class TickTrace(NamedTuple):
    """Per-tick scalar outputs of the scan (QoS time series)."""

    completed: jnp.ndarray      # requests completed this tick
    generated: jnp.ndarray      # requests generated this tick
    n_waiting: jnp.ndarray      # cloudlets in waiting queue
    n_exec: jnp.ndarray         # cloudlets in execution queue
    n_transit: jnp.ndarray      # transfers in flight on the fabric (§6)
    used_mips: jnp.ndarray      # Σ instance used mips
    active_instances: jnp.ndarray
    active_clients: jnp.ndarray


def edge_table_size(n_services: int, d_max: int, n_apis: int) -> int:
    """Length of every per-service-edge table — retry/timeout/payload on
    :class:`AppStatic` AND the FaultState breaker tables: ``S * d_max``
    call edges plus one client→entry edge per API (ids
    ``S*d_max .. S*d_max + n_apis - 1``).  ONE resolver shared by
    ``build_app`` and ``zeros_state`` so the two can never disagree."""
    return n_services * d_max + max(n_apis, 1)


def zeros_state(caps: SimCaps, params: SimParams, rng, n_services: int = 1,
                n_edges: int | None = None, n_apis: int = 1,
                app=None) -> SimState:
    """Build the initial (empty) simulation state.

    Pass ``app`` (an :class:`AppStatic`) to size the per-service-edge
    resilience tables (retry policy / circuit breaker, §7) from the app's
    own edge tables — the same ``edge_table_size`` resolver that built its
    retry/timeout/payload columns, so the FaultState tables can't be
    undersized.  Without an app, sizing falls back to the caps-derived
    bound with ``n_edges``/``n_apis`` overrides (legacy path; the engine's
    trace-time check still rejects mismatched states).

    The cloudlet pool is built to the mode-keyed :class:`PoolLayout` the
    params resolve to — exactly the columns the enabled tick phases
    declared, nothing more.  In ``faults="none"`` mode every chaos-only
    FaultState table (the [E] breaker tables, fail-slow / ejection /
    partition state) is zero-width.
    """
    caps.validate()
    validate_telemetry(params)
    validate_alerting(params)
    f32 = jnp.float32
    i32 = jnp.int32
    Nc, R, C, I, V = (caps.n_clients, caps.max_requests, caps.max_cloudlets,
                      caps.max_instances, caps.n_vms)
    if app is not None:
        n_services = int(app.n_services)
        n_edges = int(app.n_edges)
    S = n_services
    chaos = params.faults == "chaos"
    E = n_edges if n_edges is not None \
        else edge_table_size(n_services, caps.d_max, n_apis)
    if not chaos:
        E = 0     # chaos-only columns: zero-width off the Disruption phase
    layout = resolve_layout(params)
    return SimState(
        tick=jnp.zeros((), i32),
        time=jnp.zeros((), f32),
        rng=rng,
        rr=jnp.zeros((S,), i32),
        clients=Clients(wait=jnp.zeros((Nc,), i32)),
        requests=Requests(
            count=jnp.zeros((), i32),
            api=jnp.full((R,), -1, i32),
            arrival=jnp.full((R,), -1.0, f32),
            outstanding=jnp.zeros((R,), i32),
            spawned=jnp.zeros((R,), i32),
            finish=jnp.zeros((R,), f32),
            response=jnp.full((R,), -1.0, f32),
            critical_len=jnp.zeros((R,), i32),
            # the failed flag is a Disruption-phase column: zero-width in
            # faults="none" mode so it never rides the scan carry there
            failed=jnp.zeros((R if chaos else 0,), jnp.uint8),
        ),
        cloudlets=Cloudlets(
            ints=jnp.tile(jnp.asarray(layout.init_ints()[None, :]), (C, 1)),
            flts=jnp.tile(jnp.asarray(layout.init_flts()[None, :]), (C, 1)),
            layout=layout,
        ),
        instances=Instances(
            status=jnp.zeros((I,), i32),
            service=jnp.full((I,), -1, i32),
            vm=jnp.full((I,), -1, i32),
            host=jnp.full((I,), -1, i32),
            mips=jnp.zeros((I,), f32),
            limit_mips=jnp.zeros((I,), f32),
            request_mips=jnp.zeros((I,), f32),
            ram=jnp.zeros((I,), f32),
            limit_ram=jnp.zeros((I,), f32),
            bw=jnp.zeros((I,), f32),
            n_exec=jnp.zeros((I,), i32),
            used_mips=jnp.zeros((I,), f32),
            used_ram=jnp.zeros((I,), f32),
            used_bw=jnp.zeros((I,), f32),
            util_ema=jnp.zeros((I,), f32),
            usage_sum=jnp.zeros((I,), f32),
            busy_ticks=jnp.zeros((I,), i32),
        ),
        vms=VMs(
            mips=jnp.zeros((V,), f32),
            mips_used=jnp.zeros((V,), f32),
            ram=jnp.zeros((V,), f32),
            ram_used=jnp.zeros((V,), f32),
        ),
        hosts=Hosts(
            egress_scale=jnp.ones((V,), f32),
            ingress_scale=jnp.ones((V,), f32),
            cpu_scale=jnp.ones((V,), f32),
        ),
        net=NetStats(
            bytes_out=jnp.zeros((V,), f32),
            bytes_in=jnp.zeros((V,), f32),
            egress_busy=jnp.zeros((V,), f32),
            ingress_busy=jnp.zeros((V,), f32),
            transits=jnp.zeros((), i32),
            transit_sum=jnp.zeros((), f32),
            hist=jnp.zeros((caps.net_hist_buckets,), i32),
        ),
        sched=SchedState(
            inst_of_rank=jnp.full((S, caps.max_replicas), -1, i32),
            svc_replicas=jnp.zeros((S,), i32),
        ),
        svc_stats=SvcStats(
            usage_sum=jnp.zeros((S,), f32),
            finished=jnp.zeros((S,), i32),
            delay_sum=jnp.zeros((S,), f32),
            exec_sum=jnp.zeros((S,), f32),
            wait_sum=jnp.zeros((S,), f32),
        ),
        counters=Counters(*([jnp.zeros((), i32)] * 5 + [jnp.zeros((), f32)]
                            + [jnp.zeros((), i32)] * 8)),
        fault=FaultState(
            host_up=jnp.ones((V,), i32),
            nic_ok=jnp.ones((V,), i32),
            edge_open_until=jnp.zeros((E,), f32),
            edge_err_ema=jnp.zeros((E,), f32),
            edge_succ=jnp.zeros((E,), i32),
            host_slow=jnp.zeros((V if chaos else 0,), i32),
            nic_factor=jnp.ones((V if chaos else 0,), f32),
            zone_cut=jnp.zeros((V, V) if chaos else (0, 0), i32),
            inst_err_ema=jnp.zeros((I if chaos else 0,), f32),
            inst_lat_ema=jnp.zeros((I if chaos else 0,), f32),
            inst_eject_until=jnp.zeros((I if chaos else 0,), f32),
            inst_succ=jnp.zeros((I if chaos else 0,), i32),
            inst_lat_sum=jnp.zeros((I if chaos else 0,), f32),
        ),
        fstats=FaultStats(*([jnp.zeros((), i32)] * 8
                            + [jnp.zeros((), f32)]
                            + [jnp.zeros((), i32)] * 5
                            + [jnp.zeros((), f32)])),
        telemetry=_zeros_telemetry(params, R, rng),
        alerts=_zeros_alerts(params, S),
    )


def _zeros_telemetry(params: SimParams, R: int, rng) -> TelemetryState:
    """Initial telemetry state: zero-width under ``telemetry="none"``
    (the FaultState pattern — the default carry pays nothing), sized from
    the tel_* knobs under ``"stream"``.

    The 1-in-k span sample mask is drawn once here from a child key
    *folded off* the root rng under the named label ``"tel_sample"``:
    ``fold_in`` leaves the parent key untouched, so ``state.rng`` — and
    with it every simulation stream — is bit-identical with telemetry on
    or off (the golden-matrix fifth combo), and the RNG auditor sees a
    named derivation if the init path is ever recorded.
    """
    f32, i32 = jnp.float32, jnp.int32
    on = params.telemetry == "stream"
    K = len(TEL_METRIC_COLUMNS)
    NA = len(TEL_ACC_COLUMNS)
    NSI = len(TEL_SPAN_I_COLUMNS)
    NSF = len(TEL_SPAN_F_COLUMNS)
    W = params.tel_windows if on else 0
    SP = params.tel_span_cap if on else 0
    if on:
        k_sample = streams.fold_in(rng, 0, name="tel_sample")
        sample = (jax.random.uniform(k_sample, (R,))
                  < 1.0 / params.tel_span_k).astype(jnp.uint8)
    else:
        sample = jnp.zeros((0,), jnp.uint8)
    return TelemetryState(
        ring=jnp.zeros((W, K), f32),
        acc=jnp.zeros((NA if on else 0,), f32),
        win=jnp.zeros((1 if on else 0,), i32),
        span_i=jnp.zeros((SP, NSI), i32),
        span_f=jnp.zeros((SP, NSF), f32),
        span_n=jnp.zeros((1 if on else 0,), i32),
        span_drops=jnp.zeros((1 if on else 0,), i32),
        sample=sample,
    )


def _zeros_alerts(params: SimParams, S: int) -> AlertState:
    """Initial alert state: zero-width unless the Alerting stage is
    compiled in (``telemetry="stream"`` AND ``alerting="burn"``) — the
    :func:`_zeros_telemetry` pattern.  Draws no RNG: alert evaluation is
    fully deterministic recording-rule math."""
    f32, i32 = jnp.float32, jnp.int32
    on = params.telemetry == "stream" and params.alerting == "burn"
    NR = len(ALERT_RULES)
    Sa = S if on else 0
    L = params.slo_long_wins if on else 0
    AP = params.slo_event_cap if on else 0
    one = 1 if on else 0
    return AlertState(
        sli_win=jnp.zeros((L, Sa, 2), f32),
        sli_acc=jnp.zeros((Sa, 2), f32),
        win=jnp.zeros((one,), i32),
        astate=jnp.zeros((Sa, NR), i32),
        pending=jnp.zeros((Sa, NR), i32),
        fires=jnp.zeros((Sa, NR), i32),
        resolves=jnp.zeros((Sa, NR), i32),
        firing_ticks=jnp.zeros((Sa, NR), i32),
        hold_until=jnp.zeros((Sa,), f32),
        ev_time=jnp.zeros((AP,), f32),
        ev_service=jnp.zeros((AP,), i32),
        ev_rule=jnp.zeros((AP,), i32),
        ev_state=jnp.zeros((AP,), i32),
        ev_n=jnp.zeros((one,), i32),
        ev_drops=jnp.zeros((one,), i32),
    )


def np_or_jnp(x):
    """Normalize config arrays to numpy (static side) for hashing safety."""
    return np.asarray(x)
