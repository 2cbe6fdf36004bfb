"""JAX fleet engine: one jitted ``lax.scan`` per trace, ``vmap`` sweeps.

Third fleet backend (``Fleet(backend="jax")``). The whole per-tick
pipeline of the vector engine — branchless masked routing, the
``fixed`` / ``race-to-idle`` / ``schedutil`` / thermal-aware-clamp
governor passes, activation targets with cooldown, straggler hedging,
the fluid FIFO drain, ``UnitPool.charge`` power accounting, and the
stacked RC thermal Euler substeps — is a pure
``(state, traffic_t) -> (state, telemetry_t)`` function driven by
``jax.lax.scan`` and jitted once. On top, :func:`sweep` ``vmap``\\ s the
program over a stacked config axis (router choice, governor scalars,
rack-mix scalars) and shards the batch across host devices with
``pmap`` when ``--xla_force_host_platform_device_count`` exposes more
than one (see ``repro.config.set_host_device_count``).

Parity contract — **tolerance, not bitwise**. The scalar engine is the
oracle and the numpy vector engine matches it bitwise; this engine
reproduces the same arithmetic but XLA may fuse (FMA), reassociate
pairwise reductions, and schedule segment ops differently, so its
telemetry is compared against the vector engine under documented
rtol/atol bounds (``tests/test_jax_parity.py``). Float64 is mandatory:
every entry point runs inside ``jax.enable_x64(True)`` — in
default float32 the drain recurrence loses request mass far beyond
those bounds.

Two tricks make the scan exact where it matters:

* the fluid FIFO collapses to a three-term recurrence per rack —
  pending cost ``B``, cumulative submitted cost ``A``, cumulative
  effective served ``S`` (``S`` snaps to ``A`` whenever a queue
  empties, mirroring the per-request 1e-12 forgiveness of
  ``QueueWorkload``) — and request-level completions/latencies are
  reconstructed on the host from the emitted per-tick ``(work, S,
  cap, perf)`` rows, with the same boundary semantics as the queue's
  pop rule;
* traces run in fixed-size blocks of :data:`_BLOCK` ticks with a
  per-tick ``live`` mask (dead ticks pass the carry through), so one
  compiled program serves every trace length and the post-trace drain:
  when the first fully-idle drain tick is found mid-block the block is
  re-run with the mask cut at that tick, landing the carry exactly on
  the inclusive stop tick — ``play_trace`` can then continue the same
  simulation, like the other engines.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import (
    TYPE_CHECKING,
    Any,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.fleet.degrade import BRK_HALF, BRK_OPEN
from repro.fleet.engine_state import (
    GOV_FIXED,
    GOV_RACE,
    GOV_SCHED,
    FleetArrays,
    build_fleet_arrays,
)
from repro.runtime import Telemetry, latency_percentiles
from repro.runtime.result import Response

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from repro.fleet.fleet import RackConfig

__all__ = ["ROUTER_KINDS", "SweepConfig", "sweep"]

#: branchless router selector values (params["router_kind"])
ROUTER_KINDS = {"round-robin": 0, "join-shortest-queue": 1, "power-aware": 2}

#: the fluid queue's per-request forgiveness (QueueWorkload pop rule)
_EPS = 1e-12

#: relative forgiveness for cumulative-axis comparisons: the carried S
#: (effective served) and the submission prefix sum A are two different
#: float summation orders of the same history, so after an overload
#: episode they drift apart by ~eps(|A|) — far above the absolute _EPS
#: once A reaches ~1e6 cost units. Completion tests along the cumulative
#: axis therefore forgive 1e-12 relative on top of the absolute floor
#: (still orders of magnitude below any real per-request cost).
_REL = 1e-12


def _cum_tol(x: Any) -> Any:
    """Forgiveness for comparisons between cumulative served/submitted
    totals (absolute floor + relative term, see ``_REL``)."""
    return _EPS + _REL * abs(x)

#: scan block size: one compiled program serves any trace length
_BLOCK = 128


class _Dims(NamedTuple):
    """Static (hashable) shape info baked into the compiled program."""

    kmax: int
    has_thermal: bool
    nt: int
    n_groups: int
    max_sub: int
    hedge_on: bool
    # emit the extra per-tick rows (opp, w_req, c_low, w_low) the host
    # needs to expand observability state after the scan; compiled as a
    # separate program so obs-off pays nothing
    emit_obs: bool = False
    # chaos mask rows are threaded through xs and the evacuation /
    # unit-cap / floor-OPP overlays run in-scan; compiled separately so
    # a chaos-free fleet runs the exact pre-chaos program
    chaos_on: bool = False
    # graceful degradation (repro.fleet.degrade lowered in-scan):
    # deadline expiry, per-rack circuit breakers, tiered admission with
    # a retry ring. All off by default so a degrade-free fleet compiles
    # to the exact pre-degrade program.
    degrade_on: bool = False
    dg_admission: bool = False
    dg_breaker_on: bool = False
    dg_use_chaos: bool = False
    dg_tiers: int = 0
    dg_attempts: int = 1
    dg_ring_slots: int = 1
    dg_lag: int = 0


# ---------------------------------------------------------------------------
# pure per-tick pipeline (everything below runs under jit)


def _route(
    params: Dict[str, Any],
    queued: Any,
    total: Any,
    dt: Any,
    cap: Any,
    alive: Optional[Any],
) -> Any:
    """All three routers, computed branchlessly and selected by
    ``params["router_kind"]`` — which is what lets a vmapped sweep give
    every config its own router. Mirrors ``repro.fleet.router``.

    ``cap`` is the (possibly chaos-degraded) per-rack capacity;
    ``alive`` is the chaos liveness mask (``None`` statically when no
    chaos is wired, keeping the compiled program unchanged)."""
    n = cap.shape[0]
    rk = params["router_kind"]
    # round-robin: uniform spread (over live racks only under chaos)
    if alive is None:
        rr = jnp.full(n, total / n)
    else:
        n_alive = jnp.sum(alive.astype(jnp.int64))  # reprolint: ok[RPL001] int64 counter, exact in any order
        rr = jnp.where(alive, total / jnp.maximum(n_alive, 1), 0.0)
    # join-shortest-queue: water-fill on expected queueing delay
    capm = jnp.maximum(cap, 1e-12)
    work = total * dt
    delay = queued / capm
    order = jnp.argsort(delay, stable=True)
    d = jnp.take(delay, order)
    c = jnp.take(capm, order)
    q = jnp.take(queued, order)
    levels = (work + jnp.cumsum(q)) / jnp.cumsum(c)  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum; jax engine is tolerance-compared, not bitwise
    feasible = jnp.where(levels >= d, jnp.arange(n), -1)
    idx = jnp.max(feasible)
    level = jnp.where(idx < 0, levels[0], levels[jnp.maximum(idx, 0)])
    jsq = jnp.maximum(0.0, cap * level - queued) / dt
    # power-aware: pack the cheapest (J/request) racks first
    porder = params["pa_order"]
    capo = jnp.take(cap, porder)
    setpoint = capo * params["pa_util_target"]

    def greedy(tot: Any, budget: Any) -> Any:
        before = jnp.concatenate(
            [jnp.zeros(1), jnp.cumsum(budget)[:-1]]  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum mirrors PowerAwareRouter._greedy
        )
        return jnp.clip(tot - before, 0.0, budget)

    take = greedy(total, setpoint)
    rem = total - jnp.sum(take)  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
    take = take + jnp.where(rem > 1e-12, greedy(rem, capo - take), 0.0)
    rem2 = total - jnp.sum(take)  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
    # chaos: a fully-dead fleet has zero capacity — guard the spread
    # denominator (the numerator is already zero, so the quotient is 0)
    spread = rem2 * capo / jnp.maximum(jnp.sum(capo), 1e-12)  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
    take = take + jnp.where(rem2 > 1e-12, spread, 0.0)
    pa = jnp.zeros(n).at[porder].set(take)
    assign = jnp.where(rk == 0, rr, jnp.where(rk == 1, jsq, pa))
    # every router hands out nothing when there is no offered load
    return jnp.where(total > 0.0, assign, jnp.zeros(n))


def _select_opps(
    params: Dict[str, Any], dims: _Dims, opp: Any, backlog: Any, rate: Any
) -> Any:
    """Branchless twin of ``_VectorFleetEngine._select_opps`` (which
    itself mirrors the scalar governors)."""
    gk = params["gov_kind"]
    opp = jnp.where(gk == GOV_FIXED, params["fixed_opp"], opp)
    busy = (rate > 0.0) | backlog
    opp = jnp.where(
        gk == GOV_RACE,
        jnp.where(busy, params["highest"], params["nominal"]),
        opp,
    )
    # schedutil: lowest-energy OPP x unit-count search over the OPP axis
    need = rate * params["sched_headroom"]
    pos = need > 0.0
    best = params["highest"]
    bestp = jnp.full(rate.shape[0], jnp.inf)
    for c in range(dims.kmax):
        eff = params["unit_rate"] * params["perf_tab"][:, c]
        ncnt = jnp.maximum(params["min_units"], jnp.ceil(need / eff)).astype(
            jnp.int64
        )
        util = jnp.minimum(1.0, rate / (jnp.maximum(ncnt, 1) * eff))
        power = (
            ncnt * (params["p_idle"] + params["spk_tab"][:, c] * util ** params["gamma"])
            + (params["n_units"] - ncnt) * params["p_base"]
        )
        upd = (
            (c < params["K"])
            & (ncnt <= params["n_units"])
            & pos
            & (power < bestp - 1e-12)
        )
        best = jnp.where(upd, c, best)
        bestp = jnp.where(upd, power, bestp)
    opp = jnp.where(gk == GOV_SCHED, jnp.where(pos, best, 0), opp)
    # thermal-aware ceiling clamps whatever the inner governor picked
    return jnp.where(
        params["has_ceiling"], jnp.minimum(opp, params["ceiling"]), opp
    )


def _thermal_step(
    params: Dict[str, Any],
    dims: _Dims,
    t_die: Any,
    t_pcb: Any,
    latched: Any,
    pw: Any,
    dt: Any,
    fan_fail: Optional[Any] = None,
) -> Tuple[Any, Any, Any, Any, Any, Any]:
    """Stacked RC Euler step (twin of ``_StackedThermal.step``). The
    per-rack sub-step counts are data-dependent, so a ``fori_loop``
    runs to the static worst case (``ThermalLayout.max_substeps``) with
    per-rack live masks — masked racks add exact zeros.

    ``fan_fail`` (chaos, per thermal rack) pins the fan fraction to
    exactly 0.0: zero airflow, zero fan power, and the PCB resistance
    collapses to ``r_pcb0`` exactly (``1 - (1 - rmin) * 0.0 == 1``)."""
    rack_u = params["th_rack_u"]
    rack_g = params["th_rack_g"]
    group_of_u = params["th_group_of_u"]
    hottest = jax.ops.segment_max(t_pcb, rack_g, num_segments=dims.nt)
    raw_frac = (hottest - params["th_fan_low"]) / params["th_fan_span"]
    frac = jnp.clip(raw_frac, 0.0, 1.0)
    if fan_fail is not None:
        frac = jnp.where(fan_fail, 0.0, frac)
    r_pcb = params["th_r_pcb0"] * (1.0 - (1.0 - params["th_fan_rmin"]) * frac)
    tau = jnp.minimum(
        params["th_r_die"] * params["th_c_die"], r_pcb * params["th_c_pcb"]
    )
    denom = jnp.maximum(0.25 * tau, 1e-6)
    n_sub = jnp.maximum(1, (dt / denom).astype(jnp.int64) + 1)
    hh = dt / n_sub
    h_u = jnp.take(hh, rack_u)
    h_g = jnp.take(hh, rack_g)
    r_pcb_g = jnp.take(r_pcb, rack_g)
    n_sub_u = jnp.take(n_sub, rack_u)
    n_sub_g = jnp.take(n_sub, rack_g)

    def body(s: Any, st: Tuple[Any, Any]) -> Tuple[Any, Any]:
        td, tp = st
        f = (td - jnp.take(tp, group_of_u)) / params["th_r_die_u"]
        flows = jax.ops.segment_sum(f, group_of_u, num_segments=dims.n_groups)
        d_die = h_u * (pw - f) / params["th_c_die_u"]
        out = (tp - params["th_t_amb_g"]) / r_pcb_g
        d_pcb = h_g * (flows - out) / params["th_c_pcb_g"]
        td = td + jnp.where(s < n_sub_u, d_die, 0.0)
        tp = tp + jnp.where(s < n_sub_g, d_pcb, 0.0)
        return (td, tp)

    t_die, t_pcb = jax.lax.fori_loop(0, dims.max_sub, body, (t_die, t_pcb))
    trip_u = jnp.take(params["th_trip"], rack_u)
    rel_u = jnp.take(params["th_release"], rack_u)
    new_latched = jnp.where(latched, ~(t_die <= rel_u), t_die >= trip_u)
    fan_w = params["th_fan_pmax"] * frac
    max_temp = jax.ops.segment_max(t_die, rack_u, num_segments=dims.nt)
    n_thr = jax.ops.segment_sum(
        new_latched.astype(jnp.int64), rack_u, num_segments=dims.nt
    )
    return t_die, t_pcb, new_latched, fan_w, max_temp, n_thr


def _step(
    params: Dict[str, Any], dims: _Dims, carry: Dict[str, Any], x: Dict[str, Any]
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """One fleet tick. ``x["live"]`` masks the whole tick (dead ticks
    pass the carry through unchanged); ``x["is_trace"]`` marks trace
    ticks (only those append to the hedge submission ring)."""
    dt = params["dt"]
    live = x["live"]
    t = carry["t"]
    B = carry["B"]
    A = carry["A"]
    S = carry["S"]
    fresh = x["rps"] * params["trace_scale"]
    total = fresh
    # chaos overlays (compiled out entirely when dims.chaos_on is off).
    # A full-rack kill edge evacuates the rack's pending cost *before*
    # routing — exactly the scalar/vector drivers' _chaos_step order —
    # and under on_kill="respill" the evacuated mass re-enters this
    # tick's offered total through the router like any other load.
    if dims.chaos_on:
        kill_edge = x["chaos_kill"]
        evac = jnp.where(kill_edge, B, 0.0)
        B = jnp.where(kill_edge, 0.0, B)
        E_new = carry["E"] + evac
        respill_rps = params["chaos_respill"] * jnp.sum(evac) / dt  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
        total = total + respill_rps
        cap_units = jnp.maximum(params["n_units"] - x["chaos_dead"], 0)
        # routers see the degraded fleet: killed units shrink capacity,
        # a fully-dead rack advertises exactly 0.0 and alive=False
        cap_rt = params["capacity_rps"] * (
            cap_units.astype(jnp.float64)
            / params["n_units"].astype(jnp.float64)
        )
        alive: Optional[Any] = x["chaos_dead"] < params["n_units"]
    else:
        evac = E_new = None
        respill_rps = jnp.float64(0.0)
        cap_units = params["n_units"]
        cap_rt = params["capacity_rps"]
        alive = None
    # graceful degradation control plane (repro.fleet.degrade lowered
    # in-scan; compiled out entirely when dims.degrade_on is off). The
    # per-tick order mirrors Fleet._degrade_pre exactly: deadline
    # expiry on the post-evacuation queue, breaker state machine,
    # retry-ring release + tiered admission, then routing against the
    # breaker-scaled capacity. Respill bypasses admission, like the
    # host driver.
    D_new = None
    brk_scale = None
    if dims.degrade_on:
        tick = carry["dg_tick"]
        D = carry["dg_D"]
        # deadline expiry: the lag ring W holds per-tick admitted work;
        # the slot consumed at tick i was written at tick i - L, so
        # A_lag = total submitted through tick i - L. FIFO serving
        # means the un-dispatched part of that prefix is exactly the
        # past-deadline mass — the same mass QueueWorkload.expire pops.
        if dims.dg_lag > 0:
            W = carry["dg_W"]
            A_lag = carry["dg_A_lag"]
            slotL = jnp.mod(tick, dims.dg_lag)
            A_lag = A_lag + W[slotL]
            disp_x = S + D if not dims.chaos_on else S + E_new + D
            expired = jnp.clip(A_lag - disp_x, 0.0, B)
            B = B - expired
            D_new = D + expired
        else:
            W = A_lag = None
            expired = jnp.zeros_like(B)
            D_new = D
        # per-rack circuit breakers (post-expiry queue depth, chaos-
        # degraded capacity) — branchless twin of DegradeDriver's
        # _update_breakers state machine
        brk = carry["dg_brk"]
        since = carry["dg_since"]
        last_live = carry["dg_last_live"]
        opens = carry["dg_opens"]
        if dims.dg_breaker_on:
            if dims.chaos_on and dims.dg_use_chaos:
                full_dead = x["chaos_dead"] >= params["n_units"]
            else:
                full_dead = jnp.zeros(B.shape[0], bool)
            last_live = jnp.where(full_dead, last_live, tick)
            failed = (tick - last_live) > params["dg_fail_timeout_ticks"]
            delay = B / jnp.maximum(cap_rt, 1e-12)
            trip = (delay > params["dg_open_after"]) | failed
            open_now = (brk == 0) & trip
            to_half = (brk == BRK_OPEN) & (
                tick - since >= params["dg_cooldown_ticks"]
            )
            half_trip = (brk == BRK_HALF) & trip
            to_closed = (
                (brk == BRK_HALF)
                & (delay <= params["dg_close_below"])
                & ~failed
            )
            brk = jnp.where(
                open_now | half_trip,
                BRK_OPEN,
                jnp.where(to_half, BRK_HALF, jnp.where(to_closed, 0, brk)),
            )
            since = jnp.where(open_now | half_trip | to_half, tick, since)
            opens = opens + jnp.sum(  # reprolint: ok[RPL001] int64 counter, exact in any order
                (open_now | half_trip).astype(jnp.int64)
            )
            brk_scale = jnp.where(
                brk == BRK_OPEN,
                0.0,
                jnp.where(brk == BRK_HALF, params["dg_probe"], 1.0),
            )
        else:
            brk_scale = jnp.ones(B.shape[0])
        # retry-ring release + SLO-tiered admission on fleet totals
        ring = carry["dg_ring"]
        shed_by_tier = carry["dg_shed_by_tier"]
        retried = carry["dg_retried"]
        dropped = carry["dg_retry_dropped"]
        shed_row = jnp.zeros(ring.shape[1])
        retried_d = jnp.float64(0.0)
        dropped_d = jnp.float64(0.0)
        if dims.dg_admission:
            slot = jnp.mod(tick, dims.dg_ring_slots)
            released = ring[slot]  # (tiers, attempts)
            ring = ring.at[slot].set(0.0)
            cap_total = jnp.sum(cap_rt * brk_scale)  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
            queued_total = jnp.sum(B)  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
            est_delay = queued_total / jnp.maximum(cap_total, 1e-12)
            dticks = x["dg_dticks"]  # (attempts,) int64 backoff delays
            shares = params["dg_shares"]
            budgets = params["dg_budgets"]
            # tier split of the fresh trace load: the last tier takes
            # the exact remainder (DegradePolicy share semantics)
            fresh_k = []
            acc = jnp.float64(0.0)
            for k in range(dims.dg_tiers - 1):
                f_k = shares[k] * fresh
                fresh_k.append(f_k)
                acc = acc + f_k
            fresh_k.append(fresh - acc)
            admit_total = jnp.float64(0.0)
            adm_list = []  # per-tier admitted rps, for the host-side
            # tier-split reconstruction of sub-requests (mirrors the
            # fractions DegradeDriver.pre_route hands to _tier_requests)
            for k in range(dims.dg_tiers):
                rel_mass = released[k]  # (attempts,)
                rel_rps = jnp.sum(rel_mass) / dt  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
                ok = (est_delay <= budgets[k]) & (cap_total > 1e-12)
                adm_k = jnp.where(ok, fresh_k[k] + rel_rps, 0.0)
                adm_list.append(adm_k)
                admit_total = admit_total + adm_k
                shed_fresh = jnp.where(ok, 0.0, fresh_k[k] * dt)
                shed_row = shed_row.at[k].set(
                    shed_fresh + jnp.where(ok, 0.0, jnp.sum(rel_mass))  # reprolint: ok[RPL001] jax tolerance-parity: XLA reduction order is unpinned by design here
                )
                # fresh shed enters the retry ring at attempt 0
                if dims.dg_attempts > 1:
                    s0 = jnp.mod(tick + dticks[0], dims.dg_ring_slots)
                    ring = ring.at[s0, k, 1].add(shed_fresh)
                    retried_d = retried_d + shed_fresh
                else:
                    dropped_d = dropped_d + shed_fresh
                # re-shed released mass moves to the next attempt (or
                # out of budget)
                for a in range(1, dims.dg_attempts):
                    m = jnp.where(ok, 0.0, rel_mass[a])
                    if a + 1 >= dims.dg_attempts:
                        dropped_d = dropped_d + m
                    else:
                        sa = jnp.mod(tick + dticks[a], dims.dg_ring_slots)
                        ring = ring.at[sa, k, a + 1].add(m)
                        retried_d = retried_d + m
            shed_by_tier = shed_by_tier + shed_row
            retried = retried + retried_d
            dropped = dropped + dropped_d
            total = admit_total + respill_rps
        if dims.dg_breaker_on:
            cap_rt = cap_rt * brk_scale
            brk_alive = brk != BRK_OPEN
            alive = brk_alive if alive is None else alive & brk_alive
    assign = _route(params, B, total, dt, cap_rt, alive)
    work = assign * dt
    rate = work / dt
    # frequency governors pick this tick's OPP (window_s == dt_s)
    opp = _select_opps(params, dims, carry["opp"], carry["backlog"], rate)
    # a power-capped rack *runs* at the floor point this tick while the
    # carried governor state stays untouched (force_floor_opp twin)
    if dims.chaos_on:
        opp_eff = jnp.where(x["chaos_cap"] & params["has_table"], 0, opp)
    else:
        opp_eff = opp
    perf_req = jnp.take_along_axis(
        params["perf_tab"], opp_eff[:, None], axis=1
    )[:, 0]
    perf_sz = jnp.where(params["has_table"], perf_req, 1.0)
    # UnitGovernor.target_units / apply_target with group == 1
    need = rate * params["headroom"] / (
        params["unit_rate"] * jnp.maximum(perf_sz, 1e-9)
    )
    raw = jnp.minimum(
        params["n_units"], jnp.maximum(params["min_units"], jnp.ceil(need))
    )
    tgt = jnp.maximum(1, raw.astype(jnp.int64))
    active = carry["active"]
    if dims.chaos_on:
        # killed units are force-released (no cooldown stamp, no scale
        # event — a fault is not a scaling decision) and the target is
        # capped, mirroring apply_target's unit_cap path
        tgt = jnp.minimum(tgt, cap_units)
        active = jnp.minimum(active, cap_units)
    up = tgt > active
    keep_n = jnp.maximum(params["minq"], tgt)
    in_cooldown = t - carry["last_down"] > params["cooldown"]
    down = (tgt < active) & in_cooldown & (keep_n < active)
    new_active = jnp.where(up, tgt, jnp.where(down, keep_n, active))
    scale = up.astype(jnp.int64) + down.astype(jnp.int64)
    scale_events = carry["scale_events"] + scale
    last_down = jnp.where(down, t, carry["last_down"])
    k_f = new_active.astype(jnp.float64)
    # mean perf-scale over active units; trip-latched dies dragged to
    # the floor OPP (pool.perf_scale / _perf_from_opp_counts). A fully
    # killed rack has k == 0: the pool returns the requested point's
    # perf there (the k_div guard only rewrites the k == 0 lanes)
    if dims.chaos_on:
        k_div = jnp.maximum(k_f, 1.0)
        perf_used = jnp.where(
            params["has_table"],
            jnp.where(new_active > 0, (k_f * perf_req) / k_div, perf_req),
            1.0,
        )
    else:
        perf_used = jnp.where(params["has_table"], (k_f * perf_req) / k_f, 1.0)
    if dims.has_thermal:
        ti = params["t_idx"]
        rack_u = params["th_rack_u"]
        latched = carry["latched"]
        am = params["th_local_idx"] < jnp.take(new_active, ti)[rack_u]
        lam = (am & latched).astype(jnp.int64)
        c_low_t = jax.ops.segment_sum(lam, rack_u, num_segments=dims.nt)
        c_low_f = c_low_t.astype(jnp.float64)
        k_t = jnp.take(k_f, ti)
        p0 = jnp.take(params["perf_tab"][:, 0], ti)
        pr = jnp.take(perf_req, ti)
        floor_all = (jnp.take(opp_eff, ti) == 0) & (c_low_t > 0)
        mixed = c_low_f * p0 + (k_t - c_low_f) * pr
        if dims.chaos_on:
            k_div_t = jnp.maximum(k_t, 1.0)
            perf_used = perf_used.at[ti].set(
                jnp.where(
                    k_t > 0.0,
                    jnp.where(floor_all, k_t * p0, mixed) / k_div_t,
                    pr,
                )
            )
        else:
            perf_used = perf_used.at[ti].set(
                jnp.where(floor_all, k_t * p0, mixed) / k_t
            )
    # straggler hedging: the submission ring carries (cumulative cost,
    # arrival) per trace tick; the head request is the first submission
    # not yet fully served (searchsorted past S + forgiveness)
    arrival_t = t + 0.5 * dt
    A_new = A + work
    if dims.hedge_on:
        wmask = x["is_trace"] & live
        ptr = carry["ptr"]
        A_buf = carry["A_buf"]
        arr_buf = carry["arr_buf"]
        A_buf = A_buf.at[:, ptr].set(jnp.where(wmask, A_new, A_buf[:, ptr]))
        arr_buf = arr_buf.at[:, ptr].set(
            jnp.where(wmask, arrival_t, arr_buf[:, ptr])
        )
        new_ptr = ptr + wmask.astype(jnp.int64)
        # under chaos the head search skips evacuated mass: the combined
        # dispatched axis is S + E (served + voided), mirroring the
        # scalar queue being physically cleared by evacuate()
        if dims.chaos_on:
            disp = S + E_new
        else:
            disp = S
        # deadline-expired mass leaves the queue the same way (the
        # scalar queue is physically popped by expire())
        if dims.degrade_on and dims.dg_lag > 0:
            disp = disp + D_new
        head = jax.vmap(
            lambda row, key: jnp.searchsorted(row, key, side="right")
        )(A_buf, disp + _cum_tol(disp))
        hidx = jnp.minimum(head, jnp.maximum(new_ptr - 1, 0))
        head_arrival = jnp.take_along_axis(arr_buf, hidx[:, None], axis=1)[:, 0]
        age = jnp.maximum(0.0, t - head_arrival)
        pending = (B + work) > 0.0
        h = (
            pending
            & (age > params["hedge_deadline"])
            & (new_active < cap_units)
        ).astype(jnp.int64)
        if dims.chaos_on:
            # drain-tick respill is not recorded in the submission ring
            # (is_trace gates writes); without a ring entry past the
            # dispatched axis there is no head request to age
            h = h * (head < new_ptr).astype(jnp.int64)
    else:
        h = jnp.zeros_like(new_active)
    hedged = carry["hedged"] + h
    # fluid FIFO drain (QueueWorkload.step_fast collapsed to B/A/S)
    cap = (
        jnp.maximum(new_active + h, 0).astype(jnp.float64)
        * params["unit_rate"]
        * dt
        * jnp.maximum(perf_used, 0.0)
    )
    Bw = B + work
    empty = Bw <= cap + _EPS
    used = jnp.where(empty, Bw, cap)
    B_new = jnp.where(empty, 0.0, Bw - cap)
    S_new = jnp.where(empty, S + Bw, S + cap)
    cap_safe = jnp.where(cap > 0.0, cap, 1.0)
    util = jnp.where(cap > 0.0, used / cap_safe, 0.0)
    backlog = B_new > 0.0
    served = carry["served"] + used
    # UnitPool.charge: active units at the rack's OPP (latched dies at
    # the floor), the borrowed hedge unit at the requested point, the
    # rest at the gated floor
    u = jnp.clip(util, 0.0, 1.0)
    ug = u ** params["gamma"]
    spk_req = jnp.take_along_axis(
        params["spk_tab"], opp_eff[:, None], axis=1
    )[:, 0]
    w_req = params["p_idle"] + spk_req * ug
    h_f = h.astype(jnp.float64)
    powered = new_active + h
    powered_f = powered.astype(jnp.float64)
    p_act = k_f * w_req
    fan_w = jnp.zeros(w_req.shape[0])
    if dims.has_thermal:
        w_low = params["p_idle"] + params["spk_tab"][:, 0] * ug
        w_low_t = jnp.take(w_low, ti)
        w_req_t = jnp.take(w_req, ti)
        mixed_w = c_low_f * w_low_t + (k_t - c_low_f) * w_req_t
        p_act = p_act.at[ti].set(jnp.where(floor_all, k_t * w_low_t, mixed_w))
        pw = jnp.take(params["p_base"], ti)[rack_u]
        pw = jnp.where(am, w_req_t[rack_u], pw)
        pw = jnp.where(am & latched, w_low_t[rack_u], pw)
        last_u = params["th_last_unit"]
        pw = pw.at[last_u].set(
            jnp.where(jnp.take(h, ti) > 0, w_req_t, pw[last_u])
        )
        fan_fail_t = (
            jnp.take(x["chaos_fan"], ti) if dims.chaos_on else None
        )
        t_die, t_pcb, new_latched, fan_t, temp_t, thr_t = _thermal_step(
            params, dims, carry["t_die"], carry["t_pcb"], latched, pw, dt,
            fan_fail=fan_fail_t,
        )
        fan_w = fan_w.at[ti].set(fan_t)
    p_units = jnp.where(
        params["has_table"], p_act + h_f * w_req, powered_f * w_req
    )
    p_rest = (params["n_units"] - powered).astype(jnp.float64) * params["p_base"]
    total_w = params["p_shared"] + fan_w + p_units + p_rest
    energy = carry["energy"] + total_w * dt
    unit_energy = carry["unit_energy"] + p_units * dt
    pf_safe = jnp.where(powered_f > 0.0, powered_f, 1.0)
    util_agg = jnp.where(powered_f > 0.0, powered_f * u / pf_safe, 0.0)

    def keep(new: Any, old: Any) -> Any:
        return jnp.where(live, new, old)

    new_carry: Dict[str, Any] = {
        "t": keep(t + dt, t),
        # fall back to the *pre-evacuation* carry on dead ticks (the
        # local B was rewritten by the chaos kill edge above)
        "B": keep(B_new, carry["B"]),
        "A": keep(A_new, A),
        "S": keep(S_new, S),
        "opp": keep(opp, carry["opp"]),
        "backlog": keep(backlog, carry["backlog"]),
        "active": keep(new_active, active),
        "last_down": keep(last_down, carry["last_down"]),
        "scale_events": keep(scale_events, carry["scale_events"]),
        "hedged": keep(hedged, carry["hedged"]),
        "energy": keep(energy, carry["energy"]),
        "unit_energy": keep(unit_energy, carry["unit_energy"]),
        "served": keep(served, carry["served"]),
    }
    if dims.has_thermal:
        new_carry["t_die"] = keep(t_die, carry["t_die"])
        new_carry["t_pcb"] = keep(t_pcb, carry["t_pcb"])
        new_carry["latched"] = keep(new_latched, latched)
    if dims.hedge_on:
        new_carry["A_buf"] = keep(A_buf, carry["A_buf"])
        new_carry["arr_buf"] = keep(arr_buf, carry["arr_buf"])
        new_carry["ptr"] = keep(new_ptr, carry["ptr"])
    if dims.chaos_on:
        new_carry["E"] = keep(E_new, carry["E"])
    if dims.degrade_on:
        new_carry["dg_tick"] = keep(tick + 1, tick)
        new_carry["dg_brk"] = keep(brk, carry["dg_brk"])
        new_carry["dg_since"] = keep(since, carry["dg_since"])
        new_carry["dg_last_live"] = keep(last_live, carry["dg_last_live"])
        new_carry["dg_opens"] = keep(opens, carry["dg_opens"])
        new_carry["dg_ring"] = keep(ring, carry["dg_ring"])
        new_carry["dg_shed_by_tier"] = keep(
            shed_by_tier, carry["dg_shed_by_tier"]
        )
        new_carry["dg_retried"] = keep(retried, carry["dg_retried"])
        new_carry["dg_retry_dropped"] = keep(
            dropped, carry["dg_retry_dropped"]
        )
        new_carry["dg_D"] = keep(D_new, carry["dg_D"])
        if dims.dg_lag > 0:
            # the consumed slot is overwritten with this tick's routed
            # work — it will be the lagged prefix again in L ticks
            new_carry["dg_A_lag"] = keep(A_lag, carry["dg_A_lag"])
            new_carry["dg_W"] = keep(W.at[slotL].set(work), carry["dg_W"])
    ys: Dict[str, Any] = {
        "assign": assign,
        "rate": rate,
        "work": work,
        "empty": empty,
        "used": used,
        "S": S_new,
        "cap": cap,
        "perf": perf_used,
        "active": powered,
        "power": total_w,
        "util": util_agg,
        "hedge": h,
        "scale": scale,
    }
    if dims.has_thermal:
        ys["fan"] = fan_t
        ys["temp"] = temp_t
        ys["thr"] = thr_t
    if dims.chaos_on:
        ys["evac"] = evac
    if dims.degrade_on:
        # the routed (admitted) fleet total — what the host drivers
        # append to their offered series
        ys["dg_admitted"] = total
        ys["dg_shed"] = shed_row
        if dims.dg_admission:
            # per-tier admitted rps + untiered respill rps: the host
            # side rebuilds _tier_requests-compatible split fractions
            # from these so sub-request reconstruction (responses,
            # queued counts, void/expiry counts, tier latency tags)
            # matches the host engines' tiered submissions
            ys["dg_adm"] = jnp.stack(adm_list)
            ys["dg_respill"] = respill_rps
        ys["dg_expired"] = expired
        ys["dg_brk"] = brk
        ys["dg_ring_mass"] = jnp.sum(ring)  # reprolint: ok[RPL001] jax tolerance-parity: drain-idle sentinel only, compared against exact 0
        ys["dg_retried"] = retried_d
        ys["dg_retry_dropped"] = dropped_d
    if dims.emit_obs:
        ys["opp"] = opp_eff
        ys["w_req"] = w_req
        if dims.has_thermal:
            ys["c_low"] = c_low_f
            ys["w_low"] = w_low
    return new_carry, ys


def _scan_steps(
    params: Dict[str, Any],
    carry: Dict[str, Any],
    xs: Dict[str, Any],
    dims: _Dims,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    def f(c: Dict[str, Any], x: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        return _step(params, dims, c, x)

    return jax.lax.scan(f, carry, xs)


_RUN = jax.jit(_scan_steps, static_argnames=("dims",))


# ---------------------------------------------------------------------------
# static params / carry builders (shared by the engine and sweep())


def _full_load_j_per_req(racks: "Sequence[RackConfig]") -> np.ndarray:
    """Same ranking key ``Fleet`` publishes to the PowerAwareRouter."""
    return np.array(
        [
            (rc.spec.p_shared + rc.spec.n_units * rc.spec.unit.power(1.0))
            / (rc.spec.n_units * rc.unit_rate)
            for rc in racks
        ],
        float,
    )


def _base_params(
    arr: FleetArrays, dt_s: float, jpr: np.ndarray
) -> Dict[str, Any]:
    p: Dict[str, Any] = {
        "dt": float(dt_s),
        "trace_scale": 1.0,
        "router_kind": np.int64(ROUTER_KINDS["join-shortest-queue"]),
        "pa_util_target": 0.85,
        "pa_order": np.argsort(jpr, kind="stable"),
        "capacity_rps": arr.n_units.astype(float) * arr.unit_rate,
        "n_units": arr.n_units,
        "unit_rate": arr.unit_rate,
        "headroom": arr.headroom,
        "min_units": arr.min_units,
        "minq": arr.minq,
        "cooldown": arr.cooldown,
        "p_shared": arr.p_shared,
        "p_idle": arr.p_idle,
        "gamma": arr.gamma,
        "p_base": arr.p_base,
        "has_table": arr.has_table,
        "K": arr.K,
        "perf_tab": arr.perf_tab,
        "spk_tab": arr.spk_tab,
        "nominal": arr.nominal,
        "highest": arr.highest,
        "gov_kind": arr.gov_kind,
        "fixed_opp": arr.fixed_opp,
        "sched_headroom": arr.sched_headroom,
        "ceiling": arr.ceiling,
        "has_ceiling": arr.has_ceiling,
        "hedge_deadline": np.array(
            [np.inf if dl is None else float(dl) for dl in arr.hedge_deadline]
        ),
    }
    th = arr.thermal
    if th is not None:
        p.update(
            t_idx=th.t_idx,
            th_rack_u=th.rack_u,
            th_rack_g=th.rack_g,
            th_group_of_u=th.group_of_u,
            th_local_idx=th.local_idx,
            th_last_unit=th.last_unit,
            th_r_die=th.r_die,
            th_c_die=th.c_die,
            th_r_pcb0=th.r_pcb0,
            th_c_pcb=th.c_pcb,
            th_t_amb_g=th.t_amb_g,
            th_fan_low=th.fan_low,
            th_fan_span=th.fan_span,
            th_fan_rmin=th.fan_rmin,
            th_fan_pmax=th.fan_pmax,
            th_trip=th.trip,
            th_release=th.release,
            th_r_die_u=th.r_die_u,
            th_c_die_u=th.c_die_u,
            th_c_pcb_g=th.c_pcb_g,
        )
    return p


def _make_dims(
    arr: FleetArrays,
    dt_s: float,
    hedge_on: bool,
    emit_obs: bool = False,
    chaos_on: bool = False,
    degrade: Optional[Any] = None,
) -> _Dims:
    th = arr.thermal
    return _Dims(
        kmax=int(arr.Kmax),
        has_thermal=th is not None,
        nt=0 if th is None else int(len(th.t_idx)),
        n_groups=0 if th is None else th.n_groups,
        max_sub=0 if th is None else th.max_substeps(dt_s),
        hedge_on=hedge_on,
        emit_obs=emit_obs,
        chaos_on=chaos_on,
        degrade_on=degrade is not None,
        dg_admission=degrade is not None and degrade.admission_on,
        dg_breaker_on=degrade is not None and degrade.breaker_on,
        dg_use_chaos=(
            degrade is not None
            and degrade.breaker_on
            and degrade.policy.breaker.use_chaos_signal
        ),
        dg_tiers=0 if degrade is None else int(degrade.n_tiers),
        dg_attempts=(
            1 if degrade is None else int(degrade.retry.max_attempts)
        ),
        dg_ring_slots=1 if degrade is None else int(degrade.ring_slots),
        dg_lag=0 if degrade is None else int(degrade.deadline_lag),
    )


def _fresh_carry(arr: FleetArrays, hedge_on: bool, tbuf: int) -> Dict[str, Any]:
    n = arr.n_racks
    c: Dict[str, Any] = {
        "t": np.float64(0.0),
        "B": np.zeros(n),
        "A": np.zeros(n),
        "S": np.zeros(n),
        "opp": arr.opp0.copy(),
        "backlog": np.zeros(n, bool),
        "active": arr.minq.copy(),
        "last_down": np.full(n, -1e9),
        "scale_events": np.zeros(n, np.int64),
        "hedged": np.zeros(n, np.int64),
        "energy": np.zeros(n),
        "unit_energy": np.zeros(n),
        "served": np.zeros(n),
    }
    th = arr.thermal
    if th is not None:
        c["t_die"] = th.t_amb[th.rack_u].copy()
        c["t_pcb"] = th.t_amb[th.rack_g].copy()
        c["latched"] = np.zeros(th.n_flat_units, bool)
    if hedge_on:
        c["A_buf"] = np.full((n, tbuf), np.inf)
        c["arr_buf"] = np.full((n, tbuf), np.inf)
        c["ptr"] = np.int64(0)
    return c


def _host_rows(ys: Any, n: int) -> Dict[str, np.ndarray]:
    host = jax.device_get(ys)
    return {k: np.asarray(v)[:n] for k, v in host.items()}


# ---------------------------------------------------------------------------
# host-side request reconstruction (completions / latencies / queue depth)


def _expand_submissions(
    work_col: np.ndarray, split_rows: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Expand each work-carrying tick into per-tier sub-submissions,
    mirroring ``fleet._tier_requests`` exactly: slice existence is
    decided by ``frac > 0`` alone, non-last slices cost ``work * frac``
    and the last positive-fraction slice takes the exact remainder (the
    trailing column is the untiered chaos respill, tier index ``-1``
    → tier count). Returns (submission ticks, costs, tier indices)."""
    ticks: List[int] = []
    costs: List[float] = []
    tiers: List[int] = []
    for i in np.nonzero(work_col > 0.0)[0]:
        w = float(work_col[i])
        row = split_rows[i]
        idx = np.nonzero(row > 0.0)[0]
        if len(idx) == 0:
            # no split recorded for a work-carrying tick (should not
            # happen: routed work implies admitted flow) — keep the
            # mass as one untiered submission rather than drop it
            ticks.append(int(i))
            costs.append(w)
            tiers.append(len(row) - 1)
            continue
        acc = 0.0
        for k in idx[:-1]:
            c = w * float(row[k])
            ticks.append(int(i))
            costs.append(c)
            tiers.append(int(k))
            acc += c
        c = w - acc
        if c > 0.0:
            ticks.append(int(i))
            costs.append(c)
            tiers.append(int(idx[-1]))
    return (
        np.asarray(ticks, np.int64),
        np.asarray(costs),
        np.asarray(tiers, np.int64),
    )


def _completions(
    work_col: np.ndarray,
    s_col: np.ndarray,
    split_rows: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Per-rack submission ticks, cumulative-cost tails, completion
    ticks, and (when tiered) tier indices. Without ``split_rows`` one
    fluid request is reconstructed per work-carrying tick; with it,
    each tick expands into the same per-tier sub-requests the host
    engines submit via ``_tier_requests``, so response / queued / void
    *counts* match the hosts. Submission ``k`` completes at the first
    tick whose cumulative effective served ``S`` reaches its cumulative
    cost tail, minus the cumulative-axis forgiveness (``_cum_tol`` —
    the pop rule of ``QueueWorkload``, widened to relative because
    ``a`` and ``s_col`` are different float summation orders of the
    same history). A completion index of ``len(s_col)`` means "still
    queued"."""
    if split_rows is None:
        a = np.cumsum(work_col)  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum replays the device carry's sequential adds
        sub = np.nonzero(work_col > 0.0)[0]
        a_sub = a[sub]
        tiers = None
    else:
        sub, costs, tiers = _expand_submissions(work_col, split_rows)
        a_sub = np.cumsum(costs)  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum replays the device carry's sequential adds
    j = np.searchsorted(s_col, a_sub - _cum_tol(a_sub), side="left")
    return sub, a_sub, j, tiers


def _queued_for_rack(
    work_col: np.ndarray,
    s_col: np.ndarray,
    split_rows: Optional[np.ndarray] = None,
) -> np.ndarray:
    """End-of-tick queued request count per tick (len(queue) twin)."""
    t_all = len(work_col)
    sub, _, j, _ = _completions(work_col, s_col, split_rows)
    diff = np.zeros(t_all + 1, np.int64)
    np.add.at(diff, sub, 1)
    np.add.at(diff, np.minimum(j, t_all), -1)
    return np.cumsum(diff[:-1])  # reprolint: ok[RPL001] jax tolerance-parity: int64 prefix sum, exact in any order


def _responses_for_rack(
    ts: np.ndarray,
    dt: float,
    work_col: np.ndarray,
    s_col: np.ndarray,
    cap_col: np.ndarray,
    perf_col: np.ndarray,
    unit_rate: float,
    evac_col: Optional[np.ndarray] = None,
    split_rows: Optional[np.ndarray] = None,
    payloads: Optional[List[Optional[str]]] = None,
) -> List[Response]:
    """Rebuild the rack's :class:`Response` list from emitted rows,
    with ``QueueWorkload.step_fast``'s finish-time arithmetic. With
    ``split_rows``/``payloads`` (tiered admission active) each tick
    expands into the hosts' per-tier sub-requests and every Response
    carries its tier name as ``output`` — the same tagging the host
    engines get from ``QueueWorkload`` echoing ``Request.payload`` —
    so :func:`repro.fleet.degrade.tier_latency_percentiles` works on
    jax telemetry within the engine's documented tolerances.

    ``evac_col`` is the per-tick cost *voided* without being served:
    chaos evacuations (the whole pending queue flushed by a kill edge)
    plus deadline expiries (``QueueWorkload.expire``). The dispatched
    axis becomes ``S + cumsum(void)``, and a request whose cumulative
    tail lands inside its crossing tick's void jump emits no Response.
    Voiding happens *before* serving within a tick (kill edges and
    expiry both run pre-routing), so the in-tick order of the jump vs
    the served mass is void-first — a request past the jump at an
    expiry tick genuinely completed (unlike a kill tick, where the
    rack's unit cap is 0 and nothing serves)."""
    if evac_col is not None:
        s_col = s_col + np.cumsum(evac_col)  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum replays the device carry's sequential adds
    sub, a_sub, j, tiers = _completions(work_col, s_col, split_rows)
    t_all = len(ts)
    done: List[Tuple[int, int, Response]] = []
    for k in range(len(sub)):
        jj = int(j[k])
        if jj >= t_all:
            continue  # never completed (undrained overload)
        s_prev = float(s_col[jj - 1]) if jj > 0 else 0.0
        void_j = float(evac_col[jj]) if evac_col is not None else 0.0
        a_k = float(a_sub[k])
        if void_j > 0.0 and a_k - _cum_tol(a_k) <= s_prev + void_j:
            continue  # voided (evacuated or expired), not served
        # the void jump consumes no serving capacity: the mass served
        # *into* this request excludes it
        s_prev += void_j
        arrival = float(ts[sub[k]]) + 0.5 * dt
        cap_j = float(cap_col[jj])
        if cap_j > 0.0:
            frac = min(a_k - s_prev, cap_j) / cap_j
        else:
            frac = 1.0
        service_s = 1.0 / (unit_rate * max(float(perf_col[jj]), 1e-9))
        finish = max(float(ts[jj]) + frac * dt, arrival + service_s)
        out = None
        if tiers is not None and payloads is not None:
            tk = int(tiers[k])
            if 0 <= tk < len(payloads):
                out = payloads[tk]
        done.append(
            (jj, k,
             Response(rid=k, arrival_s=arrival, finish_s=finish, output=out))
        )
    done.sort(key=lambda it: (it[0], it[1]))  # completion order, FIFO in-tick
    return [resp for _, _, resp in done]


class _ThermalState:
    """Host mirror of the stacked RC state (what the sanitizer reads)."""

    def __init__(self, layout: Any) -> None:
        self.layout = layout
        self.t_die = layout.t_amb[layout.rack_u].copy()
        self.t_pcb = layout.t_amb[layout.rack_g].copy()
        self.latched = np.zeros(layout.n_flat_units, bool)


# ---------------------------------------------------------------------------
# the engine


class _JaxFleetEngine:
    """Block-scanned jit engine behind ``Fleet(backend="jax")``.

    Holds all mutable simulation state on the host between ``play``
    calls (so ``play_trace`` composes cumulatively like the other
    engines) and runs each call as jitted ``lax.scan`` blocks. Routing
    happens *in-scan* — the fleet's router object is only used to pick
    the branchless router kind, so only the built-in routers (and
    built-in governors) are supported; anything else must use
    ``backend="vector"``.
    """

    backend = "jax"

    def __init__(
        self,
        racks: "Sequence[RackConfig]",
        dt_s: float,
        idle_units_off: bool,
        router: Any,
    ) -> None:
        arr = build_fleet_arrays(racks, idle_units_off)
        if arr.generic:
            kinds = sorted({type(g).__name__ for _, g in arr.generic})
            raise ValueError(
                "backend='jax' compiles the governor passes and only "
                "supports the built-in governors (fixed / race-to-idle "
                f"/ schedutil / thermal-aware); got {kinds} — use "
                "backend='vector' for generic governors"
            )
        rname = getattr(router, "name", type(router).__name__)
        if rname not in ROUTER_KINDS:
            raise ValueError(
                "backend='jax' routes in-scan and only knows "
                f"{sorted(ROUTER_KINDS)}; got router {rname!r} — use "
                "backend='vector' for custom routers"
            )
        self.arrays = arr
        self.dt_s = float(dt_s)
        self.now = 0.0
        self.n_racks = arr.n_racks
        # sanitizer-facing static surface
        self.K = arr.K
        self.has_table = arr.has_table
        self._params = _base_params(arr, dt_s, _full_load_j_per_req(racks))
        self._params["router_kind"] = np.int64(ROUTER_KINDS[rname])
        self._params["pa_util_target"] = float(
            getattr(router, "util_target", 0.85)
        )
        self._hedge_any = arr.any_hedge
        # set by Fleet._wire_obs; rows are expanded host-side after play
        self.obs: Optional[Any] = None
        # mutable per-rack state (mirrors _fresh_carry)
        n = arr.n_racks
        self._B = np.zeros(n)
        self._A = np.zeros(n)
        self._S = np.zeros(n)
        self.opp = arr.opp0.copy()
        self._backlog = np.zeros(n, bool)
        self.active = arr.minq.copy()
        self._last_down = np.full(n, -1e9)
        self.scale_events = np.zeros(n, np.int64)
        self.hedged_cnt = np.zeros(n, np.int64)
        self.energy = np.zeros(n)
        self.unit_energy = np.zeros(n)
        self.served_acc = np.zeros(n)
        self.therm: Optional[_ThermalState] = (
            _ThermalState(arr.thermal) if arr.thermal is not None else None
        )
        self._A_buf = np.full((n, 0), np.inf)
        self._arr_buf = np.full((n, 0), np.inf)
        self._ptr = 0
        # chaos surface (inert until Fleet calls set_chaos): the lowered
        # schedule, the cumulative evacuated-cost carry, and the same
        # counters the scalar/vector engines expose to _build_telemetry
        self._chaos: Optional[Any] = None
        self.chaos_on_kill = "respill"
        self._E = np.zeros(n)
        self.chaos_dead = np.zeros(n, np.int64)
        self.chaos_fan = np.zeros(n, bool)
        self.chaos_cap = np.zeros(n, bool)
        self.chaos_evac_cost = 0.0
        self.chaos_evac_by_rack = np.zeros(n)
        self.chaos_dropped = 0
        self.chaos_dropped_cost = 0.0
        self.chaos_respilled = 0
        self.chaos_respilled_cost = 0.0
        # degrade surface (inert until Fleet calls set_degrade)
        self._degrade: Optional[Any] = None
        # cumulative per-tick emitted history (for telemetry rebuilds)
        self._t_hist: List[float] = []
        self._hist: Dict[str, List[np.ndarray]] = {}

    def set_chaos(self, lowered: Any) -> None:
        """Wire a :class:`~repro.fleet.chaos.LoweredChaos` schedule.

        Called by ``Fleet.__init__``; the schedule is re-sampled into
        per-tick mask rows (``LoweredChaos.rows``) block by block at
        ``play`` time so the jitted scan stays shape-static — the same
        compiled program serves every schedule."""
        self._chaos = lowered if lowered.any_events() else None
        self.chaos_on_kill = lowered.on_kill

    def set_degrade(self, lowered: Any) -> None:
        """Wire a :class:`~repro.fleet.degrade.LoweredDegrade` plan.

        Called by ``Fleet.__init__``. The control plane runs in-scan;
        the host keeps carry mirrors plus the same cumulative counter
        attributes :class:`~repro.fleet.degrade.DegradeDriver` exposes,
        so ``Fleet._build_telemetry`` reads either source unchanged.
        The scan routes the admitted fleet total; per-tier request
        shape is recovered host-side from the emitted ``dg_adm`` /
        ``dg_respill`` rows (see :meth:`_tier_split_rows`), so
        responses carry tier payloads and sub-request counts match the
        host engines within the documented tolerances."""
        self._degrade = lowered
        n = self.n_racks
        nt = max(lowered.n_tiers, 1)
        self._dg_ring = np.zeros(
            (lowered.ring_slots, nt, lowered.retry.max_attempts))
        self._dg_brk = np.zeros(n, np.int64)
        self._dg_since = np.zeros(n, np.int64)
        self._dg_last_live = np.full(n, -1, np.int64)
        self._dg_opens = np.int64(0)
        self._dg_shed_by_tier = np.zeros(nt)
        self._dg_retried = np.float64(0.0)
        self._dg_retry_dropped = np.float64(0.0)
        self._dg_W = np.zeros((max(lowered.deadline_lag, 1), n))
        self._dg_A_lag = np.zeros(n)
        self._dg_D = np.zeros(n)
        # telemetry mirrors (recomputed from history after every play)
        self.shed_by_tier = np.zeros(nt)
        self.shed_cost = 0.0
        self.shed_cost_t = np.zeros(0)
        self.retried_cost = 0.0
        self.retry_dropped_cost = 0.0
        self.breaker_opens = 0
        self.breaker_state_t = np.zeros((0, n), np.int64)
        self.degrade_expired = 0
        self.degrade_expired_cost = 0.0
        self.degrade_expired_by_rack = np.zeros(n)

    # -- sanitizer / Fleet.view surface ---------------------------------
    def queued_cost(self) -> np.ndarray:
        return self._B.copy()

    def active_units(self) -> np.ndarray:
        return self.active.copy()

    # -------------------------------------------------------------------
    def _carry(self, hedge_on: bool) -> Dict[str, Any]:
        c: Dict[str, Any] = {
            "t": np.float64(self.now),
            "B": self._B,
            "A": self._A,
            "S": self._S,
            "opp": self.opp,
            "backlog": self._backlog,
            "active": self.active,
            "last_down": self._last_down,
            "scale_events": self.scale_events,
            "hedged": self.hedged_cnt,
            "energy": self.energy,
            "unit_energy": self.unit_energy,
            "served": self.served_acc,
        }
        if self.therm is not None:
            c["t_die"] = self.therm.t_die
            c["t_pcb"] = self.therm.t_pcb
            c["latched"] = self.therm.latched
        if hedge_on:
            c["A_buf"] = self._A_buf
            c["arr_buf"] = self._arr_buf
            c["ptr"] = np.int64(self._ptr)
        if self._chaos is not None:
            c["E"] = self._E
        if self._degrade is not None:
            c["dg_tick"] = np.int64(len(self._t_hist))
            c["dg_brk"] = self._dg_brk
            c["dg_since"] = self._dg_since
            c["dg_last_live"] = self._dg_last_live
            c["dg_opens"] = self._dg_opens
            c["dg_ring"] = self._dg_ring
            c["dg_shed_by_tier"] = self._dg_shed_by_tier
            c["dg_retried"] = self._dg_retried
            c["dg_retry_dropped"] = self._dg_retry_dropped
            c["dg_D"] = self._dg_D
            if self._degrade.deadline_lag > 0:
                c["dg_A_lag"] = self._dg_A_lag
                c["dg_W"] = self._dg_W
        return c

    def _full(self, key: str) -> np.ndarray:
        rows = self._hist.get(key)
        if not rows:
            return np.zeros((0, self.n_racks))
        return np.concatenate(rows, axis=0)

    # -------------------------------------------------------------------
    def play(
        self, trace_rps: Sequence[float], drain: bool = True
    ) -> Tuple[np.ndarray, np.ndarray, int, Optional[bool]]:
        """Run the whole trace (plus post-trace drain) in one shot.

        Returns ``(assigned_rps, queued_rows, n_drain_ticks, drained)``
        with one row per simulated tick; ``drained`` is ``None`` when
        the call simulated no ticks at all.
        """
        with jax.enable_x64(True):
            return self._play(np.asarray(trace_rps, float), drain)

    def _play(
        self, trace: np.ndarray, drain: bool
    ) -> Tuple[np.ndarray, np.ndarray, int, Optional[bool]]:
        dt = self.dt_s
        t_len = len(trace)
        n = self.n_racks
        if self._hedge_any and t_len > 0:
            pad = np.full((n, t_len), np.inf)
            self._A_buf = np.concatenate([self._A_buf, pad], axis=1)
            self._arr_buf = np.concatenate([self._arr_buf, pad.copy()], axis=1)
        hedge_on = self._hedge_any and self._A_buf.shape[1] > 0
        chaos = self._chaos
        degrade = self._degrade
        dims = _make_dims(
            self.arrays, dt, hedge_on,
            emit_obs=self.obs is not None,
            chaos_on=chaos is not None,
            degrade=degrade,
        )
        params = self._params
        if chaos is not None or degrade is not None:
            params = dict(params)
        if chaos is not None:
            params["chaos_respill"] = np.float64(
                1.0 if self.chaos_on_kill == "respill" else 0.0
            )
        if degrade is not None:
            params["dg_shares"] = degrade.shares
            params["dg_budgets"] = degrade.budgets
            brk_cfg = degrade.policy.breaker
            if brk_cfg is not None:
                params["dg_open_after"] = np.float64(brk_cfg.open_after_s)
                params["dg_close_below"] = np.float64(brk_cfg.close_below_s)
                params["dg_probe"] = np.float64(brk_cfg.probe_fraction)
                params["dg_cooldown_ticks"] = np.int64(
                    degrade.cooldown_ticks)
                params["dg_fail_timeout_ticks"] = np.int64(
                    degrade.fail_timeout_ticks)

        def chaos_xs(t0: float) -> Dict[str, np.ndarray]:
            """Per-tick mask rows for one block starting at ``t0``.
            Live ticks are a prefix of every block, so tick ``i`` runs
            at exactly ``t0 + i*dt`` — rows beyond the live prefix are
            masked out by the scan's carry-through."""
            assert chaos is not None
            rows = chaos.rows(t0, _BLOCK, dt)
            return {
                "chaos_dead": rows["dead"],
                "chaos_fan": rows["fan_fail"],
                "chaos_cap": rows["power_cap"],
                "chaos_kill": rows["kill_edge"],
            }

        dg_xs_on = degrade is not None and degrade.admission_on

        def degrade_xs(tick0: int) -> Dict[str, np.ndarray]:
            """Retry-delay rows for one block starting at global tick
            ``tick0`` — resamplable like ``chaos_xs`` (row k depends
            only on the absolute tick index, so the drain rewind can
            reuse the block verbatim)."""
            assert degrade is not None
            return {"dg_dticks": degrade.retry_rows(tick0, _BLOCK)}

        carry = self._carry(hedge_on)
        cur_t = self.now
        tick_base = len(self._t_hist)
        zeros = np.zeros(_BLOCK)
        falses = np.zeros(_BLOCK, bool)
        kept: List[Dict[str, np.ndarray]] = []
        pos = 0
        while pos < t_len:
            blk = min(_BLOCK, t_len - pos)
            rps = np.zeros(_BLOCK)
            rps[:blk] = trace[pos : pos + blk]
            live = np.zeros(_BLOCK, bool)
            live[:blk] = True
            xs = {"rps": rps, "live": live, "is_trace": live}
            if chaos is not None:
                xs.update(chaos_xs(cur_t))
            if dg_xs_on:
                xs.update(degrade_xs(tick_base + pos))
            carry, ys = _RUN(params, carry, xs, dims=dims)
            kept.append(_host_rows(ys, blk))
            pos += blk
            cur_t += blk * dt

        def ring_idle(rows: Dict[str, np.ndarray]) -> np.ndarray:
            """Per-tick 'retry ring is empty' mask (all-true without
            degrade) — a drain tick only starts idle when no shed mass
            is still waiting for its backoff slot."""
            if degrade is None:
                return np.ones(len(rows["empty"]), bool)
            return np.asarray(rows["dg_ring_mass"]) <= 0.0

        if kept:
            all_empty = bool(
                kept[-1]["empty"][-1].all() and ring_idle(kept[-1])[-1]
            )
        else:
            all_empty = bool(np.all(self._B <= 0.0)) and (
                degrade is None or float(self._dg_ring.sum()) <= 0.0  # reprolint: ok[RPL001] zero-test only: sum()<=0 iff all nonnegative ring slots are 0, order-free
            )
        drained: Optional[bool]
        if drain:
            # keep ticking until the first tick that starts fully idle
            # (inclusive) — the same stop tick Fleet.play_trace's
            # queued/concurrency break lands on — bounded by the same
            # 10x-trace safety cap
            cap_ticks = 10 * t_len + 100
            done = 0
            found = False
            while done < cap_ticks and not found:
                blk = min(_BLOCK, cap_ticks - done)
                live = np.zeros(_BLOCK, bool)
                live[:blk] = True
                xs = {"rps": zeros, "live": live, "is_trace": falses}
                if chaos is not None:
                    # the rewind re-runs the same block with a shorter
                    # live prefix, so the rows must be reused verbatim
                    xs_chaos = chaos_xs(cur_t)
                    xs.update(xs_chaos)
                if dg_xs_on:
                    xs_dg = degrade_xs(tick_base + t_len + done)
                    xs.update(xs_dg)
                carry0 = carry
                carry, ys = _RUN(params, carry0, xs, dims=dims)
                rows = _host_rows(ys, blk)
                allm = rows["empty"].all(axis=1) & ring_idle(rows)
                start_idle = np.concatenate(([all_empty], allm[:-1]))
                idle = np.nonzero(start_idle)[0]
                if len(idle):
                    stop = int(idle[0])
                    live2 = np.zeros(_BLOCK, bool)
                    live2[: stop + 1] = True
                    xs2 = {"rps": zeros, "live": live2, "is_trace": falses}
                    if chaos is not None:
                        xs2.update(xs_chaos)
                    if dg_xs_on:
                        xs2.update(xs_dg)
                    carry, _ = _RUN(params, carry0, xs2, dims=dims)
                    kept.append({k: v[: stop + 1] for k, v in rows.items()})
                    found = True
                else:
                    kept.append(rows)
                    all_empty = bool(allm[-1])
                    done += blk
                    cur_t += blk * dt
            drained = found
        elif t_len == 0:
            drained = None
        else:
            last = kept[-1]
            drained = bool(
                last["empty"][-1].all()
                and not (last["used"][-1] > 0.0).any()
                and ring_idle(last)[-1]
            )
        # pull the final carry back into host state
        fin = jax.device_get(carry)
        self.now = float(fin["t"])
        self._B = np.asarray(fin["B"])
        self._A = np.asarray(fin["A"])
        self._S = np.asarray(fin["S"])
        self.opp = np.asarray(fin["opp"])
        self._backlog = np.asarray(fin["backlog"])
        self.active = np.asarray(fin["active"])
        self._last_down = np.asarray(fin["last_down"])
        self.scale_events = np.asarray(fin["scale_events"])
        self.hedged_cnt = np.asarray(fin["hedged"])
        self.energy = np.asarray(fin["energy"])
        self.unit_energy = np.asarray(fin["unit_energy"])
        self.served_acc = np.asarray(fin["served"])
        if self.therm is not None:
            self.therm.t_die = np.asarray(fin["t_die"])
            self.therm.t_pcb = np.asarray(fin["t_pcb"])
            self.therm.latched = np.asarray(fin["latched"])
        if hedge_on:
            self._A_buf = np.asarray(fin["A_buf"])
            self._arr_buf = np.asarray(fin["arr_buf"])
            self._ptr = int(fin["ptr"])
        if chaos is not None:
            self._E = np.asarray(fin["E"])
        if degrade is not None:
            self._dg_brk = np.asarray(fin["dg_brk"])
            self._dg_since = np.asarray(fin["dg_since"])
            self._dg_last_live = np.asarray(fin["dg_last_live"])
            self._dg_opens = np.int64(fin["dg_opens"])
            self._dg_ring = np.asarray(fin["dg_ring"])
            self._dg_shed_by_tier = np.asarray(fin["dg_shed_by_tier"])
            self._dg_retried = np.float64(fin["dg_retried"])
            self._dg_retry_dropped = np.float64(fin["dg_retry_dropped"])
            self._dg_D = np.asarray(fin["dg_D"])
            if degrade.deadline_lag > 0:
                self._dg_A_lag = np.asarray(fin["dg_A_lag"])
                self._dg_W = np.asarray(fin["dg_W"])
        # append this call's rows to the cumulative history
        if kept:
            rows_all = {k: np.concatenate([r[k] for r in kept]) for k in kept[0]}
            n_rows = int(rows_all["empty"].shape[0])
        else:
            rows_all = {}
            n_rows = 0
        t0 = self.now - n_rows * dt
        if n_rows:
            self._t_hist.extend((t0 + np.arange(n_rows) * dt).tolist())
            for k, v in rows_all.items():
                self._hist.setdefault(k, []).append(v)
        # queue depths come from the *full* history (cumulative S/A);
        # under chaos the dispatched axis is S + cumsum(evac) — a kill
        # edge drains the queue count to zero the same tick, exactly
        # like QueueWorkload.evacuate clearing the scalar queue
        work_all = self._full("work")
        s_all = self._full("S")
        # the dispatched axis adds every kind of voided mass: chaos
        # evacuations and deadline expiries both clear queued cost
        # without serving it (a kill edge zeroes B before expiry runs,
        # so the two are never nonzero on the same (tick, rack))
        evac_all = (
            self._full("evac")
            if chaos is not None and "evac" in self._hist
            else None
        )
        exp_all = (
            self._full("dg_expired")
            if degrade is not None and "dg_expired" in self._hist
            else None
        )
        void_all = None
        if evac_all is not None or exp_all is not None:
            void_all = np.zeros_like(work_all)
            if evac_all is not None:
                void_all = void_all + evac_all
            if exp_all is not None:
                void_all = void_all + exp_all
            s_all = s_all + np.cumsum(void_all, axis=0)  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum replays the device carry's sequential adds
        split_rows = self._tier_split_rows()
        if evac_all is not None:
            self._update_chaos_counters(work_all, s_all, evac_all, split_rows)
        if degrade is not None:
            self._update_degrade_counters(work_all, s_all, exp_all, split_rows)
        queued_rows = np.zeros((n_rows, n), np.int64)
        for r in range(n):
            q = _queued_for_rack(work_all[:, r], s_all[:, r], split_rows)
            if n_rows:
                queued_rows[:, r] = q[-n_rows:]
        assigned = (
            rows_all["assign"] if n_rows else np.zeros((0, n))
        )
        if chaos is not None and n_rows:
            # host mirrors of the mask state (Fleet.view / telemetry):
            # the last applied masks are the ones sampled at the final
            # tick's *start*, same as the scalar/vector drivers
            d_fin, f_fin, c_fin = chaos.masks_at(self.now - dt)
            self.chaos_dead = d_fin
            self.chaos_fan = f_fin
            self.chaos_cap = c_fin
        return assigned, queued_rows, n_rows - t_len, drained

    def _update_chaos_counters(
        self,
        work_all: np.ndarray,
        s_eff_all: np.ndarray,
        evac_all: np.ndarray,
        split_rows: Optional[np.ndarray] = None,
    ) -> None:
        """Recompute the cumulative drop/respill accounting from the
        full emitted history (idempotent across ``play`` calls).

        Costs are the evacuated mass itself; request counts come from
        the same host reconstruction that builds Response lists — a
        submission whose crossing tick carries an evacuation was voided
        by the kill, and ``on_kill`` decides which bucket it lands in.
        ``s_eff_all`` must already include the evacuation cumsum.
        ``split_rows`` (tiered admission) expands ticks into the hosts'
        per-tier sub-requests so voided *counts* match."""
        self.chaos_evac_by_rack = evac_all.sum(axis=0)  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
        self.chaos_evac_cost = float(self.chaos_evac_by_rack.sum())  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
        t_all = evac_all.shape[0]
        n_voided = 0
        for r in range(self.n_racks):
            ecol = evac_all[:, r]
            if not ecol.any():
                continue
            _, _, j, _ = _completions(work_all[:, r], s_eff_all[:, r],
                                      split_rows)
            jv = np.clip(j, 0, t_all - 1)
            n_voided += int(np.count_nonzero((j < t_all) & (ecol[jv] > 0.0)))
        if self.chaos_on_kill == "respill":
            self.chaos_respilled = n_voided
            self.chaos_respilled_cost = self.chaos_evac_cost
            self.chaos_dropped = 0
            self.chaos_dropped_cost = 0.0
        else:
            self.chaos_dropped = n_voided
            self.chaos_dropped_cost = self.chaos_evac_cost
            self.chaos_respilled = 0
            self.chaos_respilled_cost = 0.0

    def _update_degrade_counters(
        self,
        work_all: np.ndarray,
        s_eff_all: np.ndarray,
        exp_all: Optional[np.ndarray],
        split_rows: Optional[np.ndarray] = None,
    ) -> None:
        """Recompute the cumulative degradation accounting from the
        full emitted history (idempotent across ``play`` calls), under
        the same attribute names :class:`DegradeDriver` exposes.

        Expired request *counts* come from the host reconstruction: a
        submission whose crossing tick carries an expiry, with its
        cumulative tail inside that tick's voided jump, was abandoned
        past deadline rather than served. ``s_eff_all`` must already
        include every void cumsum (evacuations + expiries)."""
        if "dg_shed" in self._hist:
            shed = np.concatenate(self._hist["dg_shed"], axis=0)
            self.shed_by_tier = shed.sum(axis=0)  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
            self.shed_cost_t = shed.sum(axis=1)  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
            self.shed_cost = float(self.shed_by_tier.sum())  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
        if "dg_retried" in self._hist:
            self.retried_cost = float(
                np.sum(np.concatenate(self._hist["dg_retried"]))  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
            )
            self.retry_dropped_cost = float(
                np.sum(np.concatenate(self._hist["dg_retry_dropped"]))  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
            )
        if "dg_brk" in self._hist:
            brk = np.concatenate(self._hist["dg_brk"], axis=0)
            self.breaker_state_t = brk.astype(np.int64)
            prev = np.vstack(
                [np.zeros((1, brk.shape[1]), np.int64), brk[:-1]]
            )
            self.breaker_opens = int(
                ((brk == BRK_OPEN) & (prev != BRK_OPEN)).sum()  # reprolint: ok[RPL001] bool edge count, exact in any order
            )
        if exp_all is None:
            return
        self.degrade_expired_by_rack = exp_all.sum(axis=0)  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
        self.degrade_expired_cost = float(self.degrade_expired_by_rack.sum())  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished host rows
        t_all = exp_all.shape[0]
        n_expired = 0
        for r in range(self.n_racks):
            ecol = exp_all[:, r]
            if not ecol.any():
                continue
            s_col = s_eff_all[:, r]
            _, a_sub, j, _ = _completions(work_all[:, r], s_col, split_rows)
            for k in range(len(a_sub)):
                jj = int(j[k])
                if jj >= t_all or ecol[jj] <= 0.0:
                    continue
                s_prev = float(s_col[jj - 1]) if jj > 0 else 0.0
                a_k = float(a_sub[k])
                if a_k - _cum_tol(a_k) <= s_prev + float(ecol[jj]):
                    n_expired += 1
        self.degrade_expired = n_expired

    def _tier_split_rows(self) -> Optional[np.ndarray]:
        """Per-tick tier fractions of the routed total, shape
        ``(T, n_tiers + 1)`` (last column = untiered chaos respill) —
        the host-side mirror of the ``frac`` vector
        :meth:`DegradeDriver.pre_route` hands to ``_tier_requests``:
        ``frac[k] = admitted_k / total``, ``frac[-1] = respill / total``.
        ``None`` when tiered admission is off (reconstruction then
        keeps its one-request-per-tick fluid shape)."""
        if self._degrade is None or "dg_adm" not in self._hist:
            return None
        adm = self._full("dg_adm")  # (T, n_tiers)
        respill = self._full("dg_respill")  # (T,)
        total = self._full("dg_admitted")  # (T,)
        rows = np.zeros((adm.shape[0], adm.shape[1] + 1))
        flow = total > 0.0
        rows[flow, :-1] = adm[flow] / total[flow, None]
        rows[flow, -1] = respill[flow] / total[flow]
        return rows

    def _tier_payloads(self) -> List[Optional[str]]:
        """Tier payload names + trailing ``None`` for the untiered
        respill column — same list ``Fleet`` hands the host engines."""
        return [t.name for t in self._degrade.tiers] + [None]

    # -------------------------------------------------------------------
    def per_rack_telemetry(self) -> List[Telemetry]:
        ts = np.asarray(self._t_hist, float)
        work = self._full("work")
        s_rows = self._full("S")
        cap = self._full("cap")
        perf = self._full("perf")
        rate = self._full("rate")
        active = self._full("active")
        power = self._full("power")
        util = self._full("util")
        empty = np.zeros(0)
        th = self.arrays.thermal
        if th is not None and "temp" in self._hist:
            fan: Optional[np.ndarray] = np.concatenate(self._hist["fan"])
            temp: Optional[np.ndarray] = np.concatenate(self._hist["temp"])
            thr: Optional[np.ndarray] = np.concatenate(self._hist["thr"])
            col_of = {int(r): j for j, r in enumerate(th.t_idx)}
        else:
            fan = temp = thr = None
            col_of = {}
        evac = (
            self._full("evac")
            if self._chaos is not None and "evac" in self._hist
            else None
        )
        # deadline-expired mass voids requests the same way (see
        # _responses_for_rack's evac_col contract)
        if self._degrade is not None and "dg_expired" in self._hist:
            exp = self._full("dg_expired")
            evac = exp if evac is None else evac + exp
        split_rows = self._tier_split_rows()
        payloads = self._tier_payloads() if split_rows is not None else None
        arr = self.arrays
        out: List[Telemetry] = []
        for r in range(self.n_racks):
            responses = _responses_for_rack(
                ts,
                self.dt_s,
                work[:, r],
                s_rows[:, r],
                cap[:, r],
                perf[:, r],
                float(arr.unit_rate[r]),
                evac_col=None if evac is None else evac[:, r],
                split_rows=split_rows,
                payloads=payloads,
            )
            p50, p99 = latency_percentiles(responses)
            j = col_of.get(r)
            if j is None or temp is None or thr is None or fan is None:
                temp_r = thr_r = fan_r = empty
            else:
                temp_r = temp[:, j].copy()
                thr_r = thr[:, j].astype(float)
                fan_r = fan[:, j].copy()
            out.append(
                Telemetry(
                    time_s=ts,
                    offered_load=rate[:, r].copy(),
                    active_units=active[:, r].astype(float),
                    power_w=power[:, r].copy(),
                    utilization=util[:, r].copy(),
                    served=float(self.served_acc[r]),
                    hedged=int(self.hedged_cnt[r]),
                    scale_events=int(self.scale_events[r]),
                    p50_latency_s=p50,
                    p99_latency_s=p99,
                    energy_j=float(self.energy[r]),
                    unit_energy_j=float(self.unit_energy[r]),
                    responses=responses,
                    workload={
                        "name": arr.names[r],
                        "kind": "fluid",
                        "unit_rate": float(arr.unit_rate[r]),
                    },
                    max_temp_c=temp_r,
                    throttled_units=thr_r,
                    fan_power_w=fan_r,
                )
            )
        return out


# ---------------------------------------------------------------------------
# batched config sweeps


@dataclass
class SweepConfig:
    """One point of a batched fig15-style policy sweep.

    Scalars multiply the corresponding per-rack base arrays (so a
    heterogeneous fleet keeps its shape); ``hedge_after_s`` of ``None``
    keeps each rack's own policy deadline, ``float("inf")`` disables
    hedging for the config, any finite value overrides every rack. The
    power-aware router runs at its default ``util_target`` (0.85).
    """

    router: str = "join-shortest-queue"
    headroom_scale: float = 1.0
    sched_headroom_scale: float = 1.0
    hedge_after_s: Optional[float] = None
    unit_rate_scale: float = 1.0
    trace_scale: float = 1.0
    name: str = ""


def sweep(
    racks: "Sequence[RackConfig]",
    configs: Sequence[SweepConfig],
    trace_rps: Sequence[float],
    dt_s: float = 60.0,
    idle_units_off: bool = True,
    drain_ticks: Optional[int] = None,
) -> List[Dict[str, Any]]:
    """Run every config over the trace as **one** batched XLA program.

    The whole scan is ``vmap``-ed over the config axis and dispatched
    in chunks; with more than one host device (see
    ``repro.config.set_host_device_count``) each chunk is additionally
    ``pmap``-sharded across devices. Every config runs the full trace
    plus ``drain_ticks`` idle ticks (default ``len(trace) + 100``);
    per-config results are trimmed at each config's own drain point, so
    summaries match a per-config ``Fleet(backend="jax").play_trace``
    within jit-determinism (a config that fails to drain inside the
    window reports ``drained=False``).

    Returns one summary dict per config (same keys across configs).
    """
    trace = np.asarray(trace_rps, float)
    assert len(configs) > 0, "need at least one sweep config"
    assert len(trace) > 0, "need a non-empty trace"
    with jax.enable_x64(True):
        return _sweep(racks, list(configs), trace, dt_s, idle_units_off,
                      drain_ticks)


def _sweep(
    racks: "Sequence[RackConfig]",
    configs: List[SweepConfig],
    trace: np.ndarray,
    dt_s: float,
    idle_units_off: bool,
    drain_ticks: Optional[int],
) -> List[Dict[str, Any]]:
    arr = build_fleet_arrays(racks, idle_units_off)
    if arr.generic:
        raise ValueError(
            "sweep() only supports the built-in governors; use the "
            "vector engine for generic governors"
        )
    for cfg in configs:
        if cfg.router not in ROUTER_KINDS:
            raise ValueError(
                f"unknown sweep router {cfg.router!r}; "
                f"choose from {sorted(ROUTER_KINDS)}"
            )
    n = arr.n_racks
    t_len = len(trace)
    n_drain = t_len + 100 if drain_ticks is None else int(drain_ticks)
    total_ticks = t_len + n_drain
    n_cfg = len(configs)
    base = _base_params(arr, dt_s, _full_load_j_per_req(racks))
    base_dl = np.asarray(base["hedge_deadline"], float)
    hedge_dls = np.stack(
        [
            base_dl
            if cfg.hedge_after_s is None
            else np.full(n, float(cfg.hedge_after_s))
            for cfg in configs
        ]
    )
    hedge_on = bool(np.isfinite(hedge_dls).any())
    dims = _make_dims(arr, dt_s, hedge_on)
    params = dict(base)
    params["router_kind"] = np.array(
        [ROUTER_KINDS[cfg.router] for cfg in configs], np.int64
    )
    params["trace_scale"] = np.array(
        [float(cfg.trace_scale) for cfg in configs]
    )
    params["unit_rate"] = np.stack(
        [arr.unit_rate * cfg.unit_rate_scale for cfg in configs]
    )
    params["capacity_rps"] = np.stack(
        [
            arr.n_units.astype(float) * arr.unit_rate * cfg.unit_rate_scale
            for cfg in configs
        ]
    )
    params["headroom"] = np.stack(
        [arr.headroom * cfg.headroom_scale for cfg in configs]
    )
    params["sched_headroom"] = np.stack(
        [arr.sched_headroom * cfg.sched_headroom_scale for cfg in configs]
    )
    params["hedge_deadline"] = hedge_dls
    batched = {
        "router_kind",
        "trace_scale",
        "unit_rate",
        "capacity_rps",
        "headroom",
        "sched_headroom",
        "hedge_deadline",
    }
    axes = {k: (0 if k in batched else None) for k in params}
    carry = _fresh_carry(arr, hedge_on, t_len)
    rps = np.zeros(total_ticks)
    rps[:t_len] = trace
    live = np.ones(total_ticks, bool)
    is_trace = np.zeros(total_ticks, bool)
    is_trace[:t_len] = True
    xs = {"rps": rps, "live": live, "is_trace": is_trace}

    ndev = jax.local_device_count()
    if ndev > 1:
        per = max(1, min(4, -(-n_cfg // ndev)))
        step_sz = ndev * per
    else:
        per = 0
        step_sz = min(8, n_cfg)
    cache_key = (
        dims,
        t_len,
        total_ticks,
        ndev,
        per,
        step_sz,
        tuple(sorted(params)),
        tuple(sorted(carry)),
    )
    mapped = _MAPPED.get(cache_key)
    if mapped is None:

        def run(
            p: Dict[str, Any], c: Dict[str, Any], x: Dict[str, Any]
        ) -> Dict[str, Any]:
            _, ys = _scan_steps(p, c, x, dims)
            return _device_summary(ys, t_len, p["dt"], p["unit_rate"])

        inner = jax.vmap(run, in_axes=(axes, None, None))
        if ndev > 1:
            mapped = jax.pmap(inner, in_axes=(axes, None, None))
        else:
            mapped = jax.jit(inner)
        _MAPPED[cache_key] = mapped
    rows: List[Dict[str, np.ndarray]] = []
    i = 0
    while i < n_cfg:
        sel = list(range(i, min(i + step_sz, n_cfg)))
        n_sel = len(sel)
        sel = sel + [sel[-1]] * (step_sz - n_sel)
        pc = {
            k: (np.asarray(params[k])[sel] if k in batched else params[k])
            for k in params
        }
        if ndev > 1:
            pc = {
                k: (
                    v.reshape((ndev, per) + v.shape[1:])
                    if k in batched
                    else v
                )
                for k, v in pc.items()
            }
        host = jax.device_get(mapped(pc, carry, xs))
        host = {
            k: np.asarray(v).reshape((step_sz,) + np.asarray(v).shape[2:])[
                :n_sel
            ]
            for k, v in host.items()
        }
        rows.append(host)
        i += n_sel
    out: List[Dict[str, Any]] = []
    ci = 0
    for part in rows:
        for k in range(len(part["ticks"])):
            out.append(_format_row(configs[ci], ci, arr, part, k))
            ci += 1
    return out


#: compiled sweep programs keyed by (dims, shapes, device layout): a
#: repeated sweep() over the same fleet/trace shape reuses the XLA
#: executable instead of re-tracing a fresh closure
_MAPPED: Dict[Tuple[Any, ...], Any] = {}


def _pctl(flat: Any, n_ok: Any, q: float) -> Any:
    """``np.percentile(lat, q)`` (linear interpolation) on a sorted
    device vector padded with ``+inf`` past ``n_ok`` valid entries."""
    pos = (q / 100.0) * jnp.maximum(n_ok - 1, 0)
    lo = jnp.floor(pos).astype(jnp.int64)
    hi = jnp.ceil(pos).astype(jnp.int64)
    w = pos - lo.astype(jnp.float64)
    v = flat[lo] * (1.0 - w) + flat[hi] * w
    return jnp.where(n_ok > 0, v, 0.0)


def _device_summary(
    ys: Dict[str, Any], t_len: int, dt: Any, unit_rate: Any
) -> Dict[str, Any]:
    """Reduce one config's emitted rows to summary scalars **on the
    device**. Shipping the raw ``(ticks, racks)`` histories to the host
    and rebuilding Response objects costs ~10x the scan itself, so the
    sweep's host traffic is a dozen scalars per config: the per-config
    trim mask, roll-ups, and the latency reconstruction (the
    ``QueueWorkload`` completion/finish arithmetic of
    :func:`_responses_for_rack`, vectorized over all submissions) all
    run inside the compiled program."""
    total = ys["empty"].shape[0]
    allm = jnp.all(ys["empty"], axis=1)
    start_idle = jnp.concatenate([jnp.zeros(1, bool), allm[:-1]])
    drain_idle = start_idle[t_len:]
    drained = jnp.any(drain_idle)
    first = jnp.argmax(drain_idle)
    n_kept = jnp.where(drained, t_len + first + 1, total)
    tick = jnp.arange(total)
    tmask = tick < n_kept
    col = tmask[:, None]
    nk = n_kept.astype(jnp.float64)
    power_t = jnp.sum(jnp.where(col, ys["power"], 0.0), axis=1)  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished device rows
    energy_j = jnp.sum(power_t) * dt  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished device rows
    served = jnp.sum(jnp.where(col, ys["used"], 0.0))  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished device rows
    active_t = jnp.sum(jnp.where(col, ys["active"], 0), axis=1)  # reprolint: ok[RPL001] jax tolerance-parity: integer unit counts, exact in any order
    hedged = jnp.sum(jnp.where(col, ys["hedge"], 0))  # reprolint: ok[RPL001] jax tolerance-parity: int64 counters, exact in any order
    scale = jnp.sum(jnp.where(col, ys["scale"], 0))  # reprolint: ok[RPL001] jax tolerance-parity: int64 counters, exact in any order
    # latency reconstruction: one fluid request per work-carrying tick,
    # completion at the first tick whose cumulative served covers its
    # cumulative cost tail (minus the cumulative-axis forgiveness)
    work = jnp.where(col, ys["work"], 0.0)
    a = jnp.cumsum(work, axis=0)  # reprolint: ok[RPL001] jax tolerance-parity: prefix cumsum replays the device carry's sequential adds
    s_col = ys["S"]
    j = jax.vmap(
        lambda scol, keys: jnp.searchsorted(scol, keys, side="left"),
        in_axes=(1, 1),
        out_axes=1,
    )(s_col, a - _cum_tol(a))
    ok = (work > 0.0) & (j < n_kept)
    jc = jnp.clip(j, 0, total - 1)
    cap_j = jnp.take_along_axis(ys["cap"], jc, axis=0)
    perf_j = jnp.take_along_axis(ys["perf"], jc, axis=0)
    s_prev = jnp.where(
        jc > 0,
        jnp.take_along_axis(s_col, jnp.maximum(jc - 1, 0), axis=0),
        0.0,
    )
    safe_cap = jnp.where(cap_j > 0.0, cap_j, 1.0)
    frac = jnp.where(
        cap_j > 0.0, jnp.minimum(a - s_prev, cap_j) / safe_cap, 1.0
    )
    arrival = (tick.astype(jnp.float64) * dt + 0.5 * dt)[:, None]
    service = 1.0 / (unit_rate[None, :] * jnp.maximum(perf_j, 1e-9))
    finish = jnp.maximum(
        jc.astype(jnp.float64) * dt + frac * dt, arrival + service
    )
    lat = jnp.where(ok, finish - arrival, jnp.inf)
    flat = jnp.sort(lat.ravel())
    n_ok = jnp.sum(ok)  # reprolint: ok[RPL001] jax tolerance-parity: bool counter, exact in any order
    return {
        "ticks": n_kept,
        "drained": drained,
        "served": served,
        "energy_j": energy_j,
        "mean_power_w": jnp.sum(power_t) / nk,  # reprolint: ok[RPL001] jax tolerance-parity: post-hoc roll-up of finished device rows
        "peak_power_w": jnp.max(jnp.where(tmask, power_t, -jnp.inf)),
        "mean_active_units": jnp.sum(active_t).astype(jnp.float64) / nk,  # reprolint: ok[RPL001] jax tolerance-parity: integer unit counts, exact in any order
        "hedged": hedged,
        "scale_events": scale,
        "p50_latency_s": _pctl(flat, n_ok, 50.0),
        "p95_latency_s": _pctl(flat, n_ok, 95.0),
        "p99_latency_s": _pctl(flat, n_ok, 99.0),
    }


def _format_row(
    cfg: SweepConfig,
    ci: int,
    arr: FleetArrays,
    part: Dict[str, np.ndarray],
    k: int,
) -> Dict[str, Any]:
    energy_j = float(part["energy_j"][k])
    served = float(part["served"][k])
    return {
        "name": cfg.name or f"cfg{ci}",
        "router": cfg.router,
        "racks": arr.n_racks,
        "ticks": int(part["ticks"][k]),
        "served": served,
        "energy_j": energy_j,
        "energy_kwh": energy_j / 3.6e6,
        "tpe": served / max(energy_j, 1e-9),
        "mean_power_w": float(part["mean_power_w"][k]),
        "peak_power_w": float(part["peak_power_w"][k]),
        "mean_active_units": float(part["mean_active_units"][k]),
        "p50_latency_s": float(part["p50_latency_s"][k]),
        "p95_latency_s": float(part["p95_latency_s"][k]),
        "p99_latency_s": float(part["p99_latency_s"][k]),
        "hedged": int(part["hedged"][k]),
        "scale_events": int(part["scale_events"][k]),
        "drained": bool(part["drained"][k]),
    }
