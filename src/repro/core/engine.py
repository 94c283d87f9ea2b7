"""Simulation engine: one fused tick per paper event cycle, scanned over time.

``make_tick`` assembles the event phases of paper §3.2 —
Generation → Dispatching → Scheduling → Derivative → Scaling & Migration —
into a single jitted state transition, and ``Simulation`` wraps
``jax.lax.scan`` over it with per-tick QoS traces.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time as _time
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import streams
from ..obs import hostspans
from . import faults as faultsmod
from . import network as netmod
from . import policies
from . import scheduler
from .app import AppStatic, InstanceTemplate, build_app, validate_app
from .generator import client_phase
from .graph import ServiceGraph
from .placement import initial_allocation, migrate
from .scaling import scaling_event
from .types import (CL_EXEC, CL_TRANSIT, CL_WAITING, DynParams, INST_ON,
                    SimCaps, SimParams, SimState, TickTrace,
                    validate_alerting, validate_telemetry, zeros_state)


def make_tick(caps: SimCaps, params: SimParams,
              has_edges: bool = True, scaling: str = "cond",
              probe: Optional[Callable[[str], None]] = None) -> Callable:
    """Build the jit-able tick function (paper event cycle, vectorized).

    ``params`` supplies the *static* knobs (policy selectors — they choose
    program structure); the swept scalars (``dyn``) and the application
    description (``app``) are traced arguments, so load/threshold sweeps
    and re-parameterized graphs (calibration) reuse one compilation.

    ``scaling`` selects how the periodic scaling/migration event is
    embedded: ``"cond"`` (a per-tick ``lax.cond``, the solo-run default),
    ``"always"`` / ``"never"`` (unconditional variants — ``run_batch``
    hoists the cadence decision OUT of its vmap, where a traced cond
    would otherwise degenerate into executing the scaling body every
    tick for every sweep point).

    ``params.network`` is static: ``"uniform"`` builds exactly the legacy
    load-independent-latency program; ``"fabric"`` inserts the Transit
    phase (core/network.py) between Generation/Derivative spawns and
    Dispatching, so RPC payloads contend on host NICs (DESIGN.md §6).

    ``params.faults`` is static: ``"none"`` builds exactly the fault-free
    program; ``"chaos"`` inserts the Disruption phase (core/faults.py)
    between Generation and Transit — host crash/recovery, instance kills,
    NIC degradation, retries and circuit breakers (DESIGN.md §7).

    ``probe`` is a trace-time hook for simcheck's layout-access checker
    (repro/analysis): called with each phase name just before that
    phase's ops trace, it lets the checker attribute recorded column
    accesses to `PHASE_COLUMNS` entries.  ``None`` (the default) adds
    nothing to the traced program.

    ``params.telemetry`` is static: ``"stream"`` adds the Telemetry
    recording ops (span capture after Execute, window close after
    Trace — repro/obs, DESIGN.md §9); ``"none"`` builds the exact
    pre-observability program (the telemetry buffers are zero-width).
    """
    if params.network not in ("uniform", "fabric"):
        raise ValueError(
            f"SimParams.network must be 'uniform' or 'fabric', "
            f"got {params.network!r}")
    if params.faults not in ("none", "chaos"):
        raise ValueError(
            f"SimParams.faults must be 'none' or 'chaos', "
            f"got {params.faults!r}")
    validate_telemetry(params)
    validate_alerting(params)
    network = params.network == "fabric"
    faults_on = params.faults == "chaos"
    telemetry = params.telemetry == "stream"
    alerting = telemetry and params.alerting == "burn"
    if telemetry:
        from ..obs import telemetry as telmod
    if alerting:
        from ..obs import slo as slomod

    # Stream names for the tick's single wide split; positions are the
    # contract (split is NOT prefix-stable), names are the audit labels.
    key_names = ("carry", "gen", "spawn", "lb", "derive") \
        + (("net_gen", "net_derive") if network else ()) \
        + (("faults", "retry_len", "retry_net") if faults_on else ())

    def tick(state: SimState, dyn: DynParams, app: AppStatic
             ) -> Tuple[SimState, TickTrace]:
        # rng split counts are mode-static; the first five (seven with the
        # fabric) match the fault-free program exactly, so faults="none"
        # stays bit-identical to the pre-faults engine.
        n_keys = (7 if network else 5) + (3 if faults_on else 0)
        keys = streams.split(state.rng, n_keys, names=key_names)
        rng, k_gen, k_gen2, k_lb, k_der = (keys[0], keys[1], keys[2],
                                           keys[3], keys[4])
        k_net_g, k_net_d = (keys[5], keys[6]) if network else (None, None)
        state = state._replace(rng=rng)

        # --- Generation (paper Alg 1) ---------------------------------
        # Each phase body runs under a jax.named_scope so every eqn in
        # the lowered program carries its tick phase — pure metadata
        # (digests identical), consumed by the analysis passes (§8).
        if probe:
            probe("Generation")
        with jax.named_scope("Generation"):
            gen = client_phase(state.clients.wait, state.time,
                               state.requests.count, app.api_cdf, dyn, k_gen)
            state, gen_res = scheduler.gen_spawn(
                state, app, caps, gen.fired, gen.api, gen.wait_proposal,
                k_gen2, dyn, params=params, net_rng=k_net_g)

        # --- Disruption (chaos mode: faults, retries, breakers) ----------
        if faults_on:
            if probe:
                probe("Disruption")
            with jax.named_scope("Disruption"):
                state = faultsmod.disruption(
                    state, app, caps, params, dyn, keys[-3], keys[-2],
                    keys[-1] if network else None)

        # --- Transit (fabric mode: NIC fair-share water-filling) --------
        if network:
            if probe:
                probe("Transit")
            with jax.named_scope("Transit"):
                state = netmod.transit(state, caps, params, dyn, app)

        # --- Dispatching (waiting → execution, load-balanced) ----------
        if probe:
            probe("Dispatch")
        with jax.named_scope("Dispatch"):
            state = scheduler.dispatch(state, app, caps, params, dyn, k_lb,
                                       network=network)

        # --- Scheduling (time-shared execution + finish) ----------------
        if probe:
            probe("Execute")
        with jax.named_scope("Execute"):
            state, fin_info = scheduler.execute(state, app, caps, params,
                                                dyn)

        # --- Telemetry: span capture (execute cleared only status/rem/
        # inst, and Derive has not yet respawned over the freed slots) ---
        if telemetry:
            if probe:
                probe("Telemetry")
            with jax.named_scope("Telemetry"):
                state = telmod.record_spans(state, fin_info, params)

        # --- Alerting (SLO burn-rate rules + alert state machine) --------
        if alerting:
            if probe:
                probe("Alerting")
            with jax.named_scope("Alerting"):
                state = slomod.alert_step(state, fin_info, params, dyn, app)

        # --- Derivative (spawn successors along the service chain) ------
        if has_edges:  # static: edge-free graphs skip the spawn machinery
            if probe:
                probe("Derive")
            with jax.named_scope("Derive"):
                state = scheduler.derive(state, app, caps, fin_info, k_der,
                                         params=params, net_rng=k_net_d)

        # --- Response (critical-path completion, paper §4.3.2) ----------
        if probe:
            probe("Response")
        with jax.named_scope("Response"):
            state, n_done = scheduler.complete(state, dyn, faults=faults_on)

        # --- Scaling & Migration (paper §5) ------------------------------
        if probe:
            probe("Scaling")
        if (params.scaling_policy or params.migration_enabled) \
                and scaling != "never":

            def do_scale(st: SimState) -> SimState:
                st = scaling_event(st, app, caps, params, dyn)
                if params.migration_enabled:
                    st = migrate(st, app, caps, dyn)
                return st

            with jax.named_scope("Scaling"):
                if scaling == "always":
                    state = do_scale(state)
                else:
                    due = (state.tick % dyn.scale_interval) == \
                        (dyn.scale_interval - 1)
                    state = jax.lax.cond(due, do_scale, lambda st: st,
                                         state)

        if probe:
            probe("Trace")
        with jax.named_scope("Trace"):
            trace = TickTrace(
                completed=n_done,
                generated=gen_res.n_new_requests,
                n_waiting=jnp.sum((state.cloudlets.status == CL_WAITING)
                                  .astype(jnp.int32)),
                n_exec=jnp.sum((state.cloudlets.status == CL_EXEC)
                               .astype(jnp.int32)),
                n_transit=jnp.sum((state.cloudlets.status == CL_TRANSIT)
                                  .astype(jnp.int32)),
                used_mips=jnp.sum(state.instances.used_mips),
                active_instances=jnp.sum((state.instances.status == INST_ON)
                                         .astype(jnp.int32)),
                active_clients=gen.n_active,
            )

        # --- Telemetry: window accumulate/close (observation-only) ------
        if telemetry:
            if probe:
                probe("Telemetry")
            with jax.named_scope("Telemetry"):
                state = telmod.close_window(state, params, dyn, trace)

        state = state._replace(tick=state.tick + 1, time=state.time + dyn.dt)
        return state, trace

    return tick


@dataclasses.dataclass
class SimResult:
    state: SimState
    trace: TickTrace
    wall_time_s: float
    compile_time_s: float
    # host seconds of each stage of the call (``sim/init_state``,
    # ``sim/lookup``, ``sim/dispatch``, ...; obs/hostspans.py)
    host_s: dict = dataclasses.field(default_factory=dict)

    def trace_np(self) -> dict:
        return {k: np.asarray(v) for k, v in self.trace._asdict().items()}


def batch_item(result: SimResult, b: int) -> SimResult:
    """Slice one sweep point out of a :meth:`Simulation.run_batch` result
    (wall, compile and host-stage times are those of the whole batch)."""
    take = lambda x: x[b]
    return SimResult(state=jax.tree_util.tree_map(take, result.state),
                     trace=jax.tree_util.tree_map(take, result.trace),
                     wall_time_s=result.wall_time_s,
                     compile_time_s=result.compile_time_s,
                     host_s=result.host_s)


def stack_dyn(dyns) -> DynParams:
    """Stack per-point :class:`DynParams` into the batched pytree
    ``run_batch`` consumes (leading axis = sweep point)."""
    dyns = list(dyns)
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *dyns)


class Simulation:
    """User-facing façade (paper Fig 4 ``Application`` + ``Register``).

    >>> sim = Simulation(graph, caps=SimCaps(...), params=SimParams(...))
    >>> result = sim.run()
    """

    def __init__(self, graph: ServiceGraph,
                 caps: SimCaps | None = None,
                 params: SimParams | None = None,
                 templates: dict[str, InstanceTemplate] | None = None,
                 default_template: InstanceTemplate | None = None,
                 vm_mips: np.ndarray | None = None,
                 vm_ram: np.ndarray | None = None,
                 api_entries=None,
                 host_egress_scale: np.ndarray | None = None,
                 host_ingress_scale: np.ndarray | None = None,
                 placement_policy: int | None = None,
                 host_zone: np.ndarray | None = None,
                 host_cpu_scale: np.ndarray | None = None,
                 service_slo_ms: np.ndarray | None = None,
                 service_slo_budget: np.ndarray | None = None):
        self.graph = graph
        self.caps = caps or SimCaps()
        self.params = params or SimParams()
        V = self.caps.n_vms
        # host→zone table (failure domains for zone-correlated chaos, §7.1);
        # defaults to one zone per host inside build_app.  The per-service
        # SLO tables feed burn-rate alerting (DESIGN.md §10); -1 entries
        # fall back to the run-wide dyn.slo_ms / dyn.slo_budget.
        self.app = build_app(graph, templates, default_template, api_entries,
                             n_hosts=V, host_zone=host_zone,
                             slo_target_ms=service_slo_ms,
                             slo_budget=service_slo_budget)
        # fail on out-of-range ids NOW, with the offending entry named,
        # instead of silently corrupting goldens at run time (§8)
        validate_app(self.app, self.caps)
        self.vm_mips = np.asarray(
            vm_mips if vm_mips is not None
            else np.full(V, 32_000.0), np.float32)
        self.vm_ram = np.asarray(
            vm_ram if vm_ram is not None
            else np.full(V, 65_536.0), np.float32)
        if len(self.vm_mips) != V or len(self.vm_ram) != V:
            raise ValueError("vm_mips/vm_ram must have n_vms entries")
        # One NIC-attached host per VM slot (network fabric, DESIGN.md §6);
        # the scales shape a heterogeneous fabric while the traced
        # nic_{egress,ingress}_mbps scalars stay sweepable.
        self.host_egress_scale = np.asarray(
            host_egress_scale if host_egress_scale is not None
            else np.ones(V), np.float32)
        self.host_ingress_scale = np.asarray(
            host_ingress_scale if host_ingress_scale is not None
            else np.ones(V), np.float32)
        # CPU-speed analogue of the NIC scales: instances on host h run at
        # cpu_scale[h] × their allocated MIPS (heterogeneous-hardware
        # studies, e.g. examples/hetero_study.py); placement still sees
        # the full requested milicores.
        self.host_cpu_scale = np.asarray(
            host_cpu_scale if host_cpu_scale is not None
            else np.ones(V), np.float32)
        if len(self.host_egress_scale) != V \
                or len(self.host_ingress_scale) != V \
                or len(self.host_cpu_scale) != V:
            raise ValueError("host NIC/CPU scales must have n_vms entries")
        self.placement_policy = (policies.PLACE_MOST_AVAILABLE
                                 if placement_policy is None
                                 else placement_policy)
        self._has_edges = bool(np.asarray(graph.n_succ).sum() > 0)
        # a call made with 0 < p < 1: its run's branch counters are read
        rp = graph.route_p
        self._conditional = rp is not None and bool(
            ((rp > 0) & (rp < 1)).any())
        self._tick = make_tick(self.caps, self.params, self._has_edges)
        # the executable the latest run / run_batch call ran
        self.last_compiled = None
        # init_state's programs and run's DynParams (see those methods)
        self._state_programs: dict = {}
        self._dyn_cache: dict = {}

    # ------------------------------------------------------------------
    def _default_device_key(self) -> str:
        """The device a fresh array lands on now (``jax.default_device``
        steers it), in :meth:`_device_key`'s form."""
        dev = jax.config.jax_default_device
        if dev is None or isinstance(dev, str):
            dev = jax.local_devices(backend=dev)[0]
        return f"{dev.platform}:{dev.id}"

    def _placement(self) -> dict:
        """The seed-independent part of the initial state, by state group
        and leaf: Algorithm 3's placement (paper §5.1) with the VMs' usage
        sums, and the VM and host tables.  Small host NumPy tables."""
        inst, iof, reps = initial_allocation(
            np.asarray(self.app.tmpl_replicas),
            np.asarray(self.app.tmpl_mips),
            np.asarray(self.app.tmpl_limit_mips),
            np.asarray(self.app.tmpl_ram),
            np.asarray(self.app.tmpl_limit_ram),
            np.asarray(self.app.tmpl_bw),
            self.vm_mips, self.vm_ram, self.caps,
            policy=self.placement_policy)
        placed = inst["vm"] >= 0
        vm_used_m = np.zeros_like(self.vm_mips)
        vm_used_r = np.zeros_like(self.vm_ram)
        np.add.at(vm_used_m, inst["vm"][placed], inst["mips"][placed])
        np.add.at(vm_used_r, inst["vm"][placed], inst["ram"][placed])
        return dict(
            instances=inst,
            vms=dict(mips=self.vm_mips, ram=self.vm_ram,
                     mips_used=vm_used_m, ram_used=vm_used_r),
            sched=dict(inst_of_rank=iof, svc_replicas=reps),
            hosts=dict(egress_scale=self.host_egress_scale,
                       ingress_scale=self.host_ingress_scale,
                       cpu_scale=self.host_cpu_scale))

    def init_state(self, seed: Optional[int] = None) -> SimState:
        """The initial state of a run from ``seed`` (``params.seed`` if
        None), built on the default device by one compiled program.

        The program traces ``zeros_state`` with :meth:`_placement`'s
        tables written over it, once per device and program structure.
        The tables are its constants; the seed is its one argument, in
        the integer type ``jax.random.PRNGKey`` turns a Python int into,
        so the key built inside equals the eager ``PRNGKey(seed)`` and a
        new seed never recompiles.  Every leaf comes back in a buffer of
        its own, so the state can be donated as it is."""
        seed = np.int64(self.params.seed if seed is None else seed)
        seed = seed.astype(jax.dtypes.canonicalize_dtype(np.int64))
        key = (self._default_device_key(), self._static_key(),
               self._shape_key(self.app), seed.dtype)
        stats = Simulation._stats
        program = self._state_programs.get(key)
        if program is None:
            tables = self._placement()

            def init_state(s):
                state = zeros_state(self.caps, self.params,
                                    jax.random.PRNGKey(s), app=self.app)
                return state._replace(**{
                    group: getattr(state, group)._replace(
                        **{k: jnp.asarray(v) for k, v in leaves.items()})
                    for group, leaves in tables.items()})

            program = self._state_programs[key] = jax.jit(init_state)
            stats["state_compiles"] += 1
        stats["state_programs"] += 1
        return program(seed)

    def _dyn_params(self) -> DynParams:
        """``DynParams.from_params(self.params)``, built once per device
        and parameter set: the run program donates only the state, so the
        same buffers serve every job."""
        key = (self._default_device_key(), self.params)
        dyn = self._dyn_cache.get(key)
        if dyn is None:
            dyn = self._dyn_cache[key] = DynParams.from_params(self.params)
        else:
            Simulation._stats["dyn_cache_hits"] += 1
        return dyn

    # ------------------------------------------------------------------
    # One compiled executable per (static knobs × pytree shapes); swept
    # scalars (dyn) and graph parameterizations (app) are traced arguments.
    _compiled_cache: dict = {}

    # Process-wide counters of the engine's host side (``stats()``):
    # calls, in-memory program cache hits and misses, the seconds the
    # misses took (lower + compile or persistent-cache load), and the
    # backend compiles and persistent-cache hits inside the calls' spans
    # (eager ops' included); the states init_state's program built and
    # the misses of its cache, and run's DynParams cache hits.
    _STATS_ZERO = dict(runs=0, program_cache_hits=0, program_compiles=0,
                       compile_s=0.0, backend_compiles=0,
                       persistent_cache_hits=0, state_programs=0,
                       state_compiles=0, dyn_cache_hits=0)
    _stats: dict = dict(_STATS_ZERO)

    @staticmethod
    def stats() -> dict:
        """A copy of the engine's process-wide counters."""
        return dict(Simulation._stats)

    @staticmethod
    def reset_stats() -> None:
        Simulation._stats = dict(Simulation._STATS_ZERO)

    @staticmethod
    def _shape_key(tree) -> tuple:
        return tuple((tuple(x.shape), str(x.dtype))
                     for x in jax.tree_util.tree_leaves(tree))

    @staticmethod
    def _device_key(state: SimState) -> str:
        """The device the run's fresh state lives on (``tpu:0``,
        ``cpu:0``; ``jax.default_device`` steers it).  Part of every
        compiled-cache key: an executable compiled for one backend is
        never handed to a run on another, such as a CPU reference run in
        a process that also drives the chip."""
        dev, = jax.tree_util.tree_leaves(state)[0].devices()
        return f"{dev.platform}:{dev.id}"

    # every SimParams knob that selects program structure (anything NOT
    # carried by the traced DynParams sweep) — cache keys and run_batch
    # validation both derive from this list.  seed is deliberately absent:
    # it only feeds init_state's PRNGKey, so seed-only changes reuse the
    # compiled executable.
    _STATIC_FIELDS = ("lb_policy", "share_policy", "scaling_policy",
                      "migration_enabled", "n_ticks", "use_pallas_tick",
                      "pallas_interpret", "network", "waterfill_iters",
                      "net_hist_bin_s", "faults", "egress_shaping",
                      "telemetry", "tel_window_ticks", "tel_windows",
                      "tel_span_k", "tel_span_cap", "tel_span_tick_cap",
                      "alerting",
                      "slo_short_wins", "slo_long_wins", "slo_for_ticks",
                      "slo_event_cap")
    # NOTE: hs_mode is deliberately NOT static — it rides DynParams as an
    # integer selector so one run_batch sweep compares util-threshold vs
    # burn-rate control planes without recompiling.

    def _static_key(self) -> tuple:
        p = self.params
        return (self.caps, self._has_edges, p.max_concurrent > 0,
                tuple(getattr(p, f) for f in self._STATIC_FIELDS))

    def _make_run_fn(self) -> Callable:
        """The solo-run program: a plain tick scan, or — telemetry on —
        the chunked scan-of-scan whose chunk boundaries flush half the
        metric ring through the io_callback tap (obs/telemetry.py).
        Exposed so simcheck's jaxpr lint walks the REAL hot-loop program
        (incl. the declared callback site), not a stand-in."""
        tick = self._tick
        n_ticks = self.params.n_ticks
        if self.params.telemetry != "stream":

            def run_fn(st: SimState, dp: DynParams, app: AppStatic):
                return jax.lax.scan(lambda s, _: tick(s, dp, app), st,
                                    None, length=n_ticks)

            return run_fn
        from ..obs import telemetry as telmod
        params = self.params

        def run_fn(st: SimState, dp: DynParams, app: AppStatic):
            return telmod.chunked_scan(lambda s, _: tick(s, dp, app),
                                       st, params, n_ticks)

        return run_fn

    def _get_compiled(self, state: SimState, dyn: DynParams):
        from ..analysis.annotate import checked_mode
        checked = checked_mode()
        key = (self._device_key(state), self._static_key(), checked,
               self._shape_key((state, dyn, self.app)))
        return self._cached(key, lambda: self._compile(state, dyn, checked))

    @staticmethod
    def _cached(key: tuple, build: Callable):
        """(program, compile seconds): the cached program under ``key``,
        or ``build()``'s, timed inside a ``sim/compile`` span, on a
        miss."""
        stats = Simulation._stats
        hit = Simulation._compiled_cache.get(key)
        if hit is not None:
            stats["program_cache_hits"] += 1
            return hit, 0.0
        with hostspans.span("sim/compile"):
            t0 = _time.perf_counter()
            compiled = build()
            dt = _time.perf_counter() - t0
        stats["program_compiles"] += 1
        stats["compile_s"] += dt
        Simulation._compiled_cache[key] = compiled
        return compiled, dt

    def _compile(self, state: SimState, dyn: DynParams, checked: bool):
        run_fn = self._make_run_fn()

        if checked:
            # REPRO_CHECKED=1: functionalize the declared-invariant asserts
            # (annotate.disjoint sites) into a checkify error carried
            # through the scan; run() throws on the first violated one.
            # No donation — checkify's error prefix changes the arity.
            from jax.experimental import checkify
            run_fn = checkify.checkify(run_fn,
                                       errors=checkify.user_checks)
            return jax.jit(run_fn).lower(state, dyn, self.app).compile()
        else:
            # The input state is consumed: run() builds a fresh one per
            # call, so the [C,*] pool blocks alias the output instead of
            # doubling resident bytes.  (Batch paths can't donate — their
            # [B,...] outputs don't match the unbatched input shapes.)
            # simcheck's jaxpr lint enforces this stays donated.
            return (jax.jit(run_fn, donate_argnums=0)
                    .lower(state, dyn, self.app).compile())

    @staticmethod
    def _unalias(state: SimState) -> SimState:
        """Copy state leaves that share a device buffer with an earlier
        leaf: donating the same buffer twice is an XLA error.  The guard
        of ``run``'s donation; a state from :meth:`init_state`'s program
        has a buffer per leaf, so it walks the pointers and copies
        nothing."""
        leaves, treedef = jax.tree_util.tree_flatten(state)
        seen: set = set()
        out = []
        for x in leaves:
            try:
                ptr = x.unsafe_buffer_pointer()
            except Exception:
                ptr = None
            if ptr is not None and ptr in seen:
                x = jnp.array(x, copy=True)
            elif ptr is not None:
                seen.add(ptr)
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    @contextlib.contextmanager
    def _job(self, name: str, seed: Optional[int]
             ) -> Iterator[hostspans.Record]:
        """The outermost host span of one ``run`` / ``run_batch`` call
        (ids: the process-wide run number and the seed); its compile
        events go into the counters."""
        stats = Simulation._stats
        stats["runs"] += 1
        rec = None
        try:
            with hostspans.span(name, run=stats["runs"], seed=int(
                    self.params.seed if seed is None else seed)) as rec:
                yield rec
        finally:
            if rec is not None:
                stats["backend_compiles"] += sum(rec.compiles.values())
                stats["persistent_cache_hits"] += sum(
                    rec.cache_hits.values())

    def _drain_telemetry(self, out_state: SimState, dyn: DynParams) -> None:
        if self.params.telemetry != "stream":
            return
        from ..obs import telemetry as telmod
        with hostspans.span("sim/drain"):
            telmod.drain_to_exporter(out_state, self.params)
            if self.params.alerting == "burn":
                from ..obs import slo as slomod
                slomod.drain_to_exporter(out_state, self.params,
                                         tags=np.asarray(dyn.tel_tag))

    def run(self, seed: Optional[int] = None) -> SimResult:
        """Compile (AOT, timed separately) and execute the full scan."""
        from ..analysis.annotate import checked_mode
        with self._job("sim/run", seed) as rec:
            with hostspans.span("sim/init_state"):
                state = self.init_state(seed)
            with hostspans.span("sim/unalias"):
                state = self._unalias(state)
            with hostspans.span("sim/dyn_params"):
                dyn = self._dyn_params()
            with hostspans.span("sim/lookup"):
                compiled, compile_s = self._get_compiled(state, dyn)
            self.last_compiled = compiled
            t1 = _time.perf_counter()
            with hostspans.span("sim/dispatch"):
                out = compiled(state, dyn, self.app)
            with hostspans.span("sim/wait"):
                if checked_mode():
                    err, (out_state, trace) = out
                    err.throw()
                else:
                    out_state, trace = out
                out_state = jax.block_until_ready(out_state)
            t2 = _time.perf_counter()
            self._count_branches(rec, out_state)
            self._drain_telemetry(out_state, dyn)
        return SimResult(state=out_state, trace=trace,
                         wall_time_s=t2 - t1, compile_time_s=compile_s,
                         host_s=dict(rec.seconds))

    def _count_branches(self, rec: hostspans.Record,
                        out_state: SimState) -> None:
        """Copy the conditional calls' counters (summed over sweep
        points) onto the job's record, where the graph has such calls."""
        if self._conditional:
            ctr = out_state.counters
            rec.counts.update(
                branch_taken=int(np.sum(ctr.branch_taken)),
                branch_skipped=int(np.sum(ctr.branch_skipped)))

    # ------------------------------------------------------------------
    def _hoists_scaling(self, dyn_b: DynParams) -> bool:
        """Whether the batch program takes the scaling cadence out of the
        vmap.  A traced cond under vmap becomes a select that executes the
        whole scaling body every tick for every sweep point.  When the
        sweep shares one scale_interval (checked on the concrete values)
        the batched program scans ticks at the outer level and conds
        between vmapped scaling/plain tick variants; otherwise it falls
        back to the per-point cond."""
        has_scaling = bool(self.params.scaling_policy
                           or self.params.migration_enabled)
        si = np.asarray(dyn_b.scale_interval)
        return has_scaling and bool((si == si.flat[0]).all())

    def _make_batch_run_fn(self, B: int, hoist: bool,
                           batched_app: bool) -> Callable:
        """The ``run_batch`` program over ``B`` sweep points (exposed, like
        :meth:`_make_run_fn`, so it can be lowered on its own)."""
        n_ticks = self.params.n_ticks
        # app axis: batched sweeps vmap over (dyn, app); plain sweeps close
        # over the one shared app (in_axes None keeps it unbatched)
        app_ax = 0 if batched_app else None
        tel_on = self.params.telemetry == "stream"
        params = self.params
        if tel_on:
            # the flush must NOT sit under a traced cond (vmap-of-cond
            # rejects IO effects): both batch paths chunk their scans and
            # flush unconditionally between chunks — under vmap the tap
            # fires once per sweep point per chunk, rows tagged by lane
            from ..obs import telemetry as telmod

        if hoist:
            tick_on = make_tick(self.caps, self.params, self._has_edges,
                                scaling="always")
            tick_off = make_tick(self.caps, self.params, self._has_edges,
                                 scaling="never")

            def run_fn(st: SimState, dp_b: DynParams, app: AppStatic):
                st_b = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (B,) + x.shape), st)
                interval = dp_b.scale_interval[0]
                on = jax.vmap(lambda s, d, a: tick_on(s, d, a),
                              in_axes=(0, 0, app_ax))
                off = jax.vmap(lambda s, d, a: tick_off(s, d, a),
                               in_axes=(0, 0, app_ax))

                def body(carry, _):
                    due = (carry.tick[0] % interval) == (interval - 1)
                    return jax.lax.cond(due, lambda s: on(s, dp_b, app),
                                        lambda s: off(s, dp_b, app), carry)

                if tel_on:
                    flush_b = jax.vmap(lambda s: telmod.flush(s, params))
                    states, traces = telmod.chunked_scan(
                        body, st_b, params, n_ticks, flush_fn=flush_b)
                else:
                    states, traces = jax.lax.scan(body, st_b, None,
                                                  length=n_ticks)
                # traces come out [T, B]; match the scan-inside-vmap layout
                return states, jax.tree_util.tree_map(
                    lambda x: jnp.swapaxes(x, 0, 1), traces)
            return run_fn

        tick = self._tick

        def run_fn(st: SimState, dp_b: DynParams, app: AppStatic):
            def one(dp: DynParams, app_p: AppStatic):
                tick_fn = lambda s, _: tick(s, dp, app_p)
                if tel_on:
                    return telmod.chunked_scan(tick_fn, st, params,
                                               n_ticks)
                return jax.lax.scan(tick_fn, st, None, length=n_ticks)
            return jax.vmap(one, in_axes=(0, app_ax))(dp_b, app)

        return run_fn

    def _get_compiled_batch(self, state: SimState, dyn_b: DynParams,
                            app_b: AppStatic | None = None):
        hoist = self._hoists_scaling(dyn_b)
        batched_app = app_b is not None
        app_arg = app_b if batched_app else self.app
        key = ("batch", self._device_key(state), hoist, batched_app,
               self._static_key(), self._shape_key((state, dyn_b, app_arg)))

        def build():
            B = np.asarray(dyn_b.dt).shape[0]
            run_fn = self._make_batch_run_fn(B, hoist, batched_app)
            return jax.jit(run_fn).lower(state, dyn_b, app_arg).compile()

        return self._cached(key, build)

    def _check_static_point(self, p: SimParams, b: int) -> None:
        """A sweep point may only vary the DynParams-traced scalars: the
        compiled program keeps ``self.params``' structure, so a mismatch in
        a structural knob would silently run the wrong program."""
        bad = [f for f in self._STATIC_FIELDS
               if getattr(p, f) != getattr(self.params, f)]
        if (p.max_concurrent > 0) != (self.params.max_concurrent > 0):
            bad.append("max_concurrent (capped vs uncapped)")
        if bad:
            raise ValueError(
                f"run_batch sweep point {b} differs from the Simulation's "
                f"params in structural knob(s) {bad}; these select program "
                "structure and cannot be swept — build a separate "
                "Simulation instead")
        if p.seed != self.params.seed:
            raise ValueError(
                f"run_batch sweep point {b} has a different seed; every "
                "point starts from the same initial state — pass seed= to "
                "run_batch (or run separate simulations) instead")

    def run_batch(self, dyn_batch, seed: Optional[int] = None,
                  apps=None) -> SimResult:
        """Run a whole parameter sweep as ONE compile + ONE device dispatch.

        ``dyn_batch`` is either a batched :class:`DynParams` (every leaf
        carries a leading sweep axis) or a sequence of per-point
        :class:`DynParams` / :class:`SimParams` which is stacked here.
        Every sweep point starts from the same initial state (same seed),
        so point ``b`` of the result equals ``run()`` with that point's
        dyn values.  Structure-changing knobs (policy selectors, pool
        sizes, ``n_ticks``) are static — sweep those with separate
        Simulations.

        ``apps`` optionally supplies one :class:`AppStatic` per sweep
        point (every leaf must match ``self.app``'s shape — e.g. re-zoned
        ``host_zone`` tables for a blast-radius study, or re-parameterized
        length/payload models for calibration); the whole sweep still
        compiles and dispatches once, vmapped over (dyn, app).
        """
        with self._job("sim/run_batch", seed) as rec:
            with hostspans.span("sim/dyn_params"):
                dyn_batch, app_b = self._batch_inputs(dyn_batch, apps)
            with hostspans.span("sim/init_state"):
                state = self.init_state(seed)
            with hostspans.span("sim/lookup"):
                compiled, compile_s = self._get_compiled_batch(
                    state, dyn_batch, app_b)
            self.last_compiled = compiled
            t1 = _time.perf_counter()
            with hostspans.span("sim/dispatch"):
                out_state, trace = compiled(
                    state, dyn_batch, app_b if app_b is not None else self.app)
            with hostspans.span("sim/wait"):
                out_state = jax.block_until_ready(out_state)
            t2 = _time.perf_counter()
            self._count_branches(rec, out_state)
            self._drain_telemetry(out_state, dyn_batch)
        return SimResult(state=out_state, trace=trace,
                         wall_time_s=t2 - t1, compile_time_s=compile_s,
                         host_s=dict(rec.seconds))

    def _batch_inputs(self, dyn_batch, apps):
        """(batched DynParams, batched AppStatic or None) of a
        ``run_batch`` call, validated."""
        if not isinstance(dyn_batch, DynParams):
            points = list(dyn_batch)
            for b, d in enumerate(points):
                if isinstance(d, SimParams):
                    self._check_static_point(d, b)
            dyn_batch = stack_dyn(
                d if isinstance(d, DynParams) else DynParams.from_params(d)
                for d in points)
        B = int(np.asarray(dyn_batch.dt).shape[0])
        app_b = None
        if apps is not None:
            apps = list(apps)
            if len(apps) != B:
                raise ValueError(
                    f"apps must supply one AppStatic per sweep point: got "
                    f"{len(apps)} apps for {B} points")
            ref = self._shape_key(self.app)
            for b, a in enumerate(apps):
                if self._shape_key(a) != ref:
                    raise ValueError(
                        f"apps[{b}] has different array shapes than the "
                        "Simulation's app; shape-changing graphs need a "
                        "separate Simulation")
            app_b = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *apps)
        if self.params.telemetry == "stream":
            # auto-tag streamed rows by sweep point unless the caller
            # already assigned tags (tag is a traced DynParams scalar)
            tags = np.asarray(dyn_batch.tel_tag)
            if np.all(tags == 0.0):
                dyn_batch = dyn_batch._replace(
                    tel_tag=jnp.arange(B, dtype=jnp.float32))
        return dyn_batch, app_b

    # Convenience accessors -------------------------------------------
    def responses(self, result: SimResult) -> np.ndarray:
        r = np.asarray(result.state.requests.response)
        return r[r >= 0]
