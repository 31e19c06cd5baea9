"""Quickstart: wavelength arbitration in a few lines.

Builds the paper's default 8-channel DWDM system (Table I), evaluates the
wavelength-oblivious arbitration schemes against their ideal policies, and
prints the robustness metrics (AFP / CAFP) across tuning ranges — the whole
TR axis in ONE jitted call through the declarative sweep frontend:

  * ``Variations``  — all device-variation / tuning-range overrides in one
    frozen pytree (``Variations(tr_mean=5.0, sigma_rlv=2.24)``);
  * ``SweepRequest`` — a declarative grid evaluation (cfg, units, axes,
    fixed overrides, scheme/policy) consumed by ``sweep(request)``;
  * results carry their axis metadata: ``res.axis("tr_mean")`` returns the
    coordinates the grid was evaluated over.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (
    ArbitrationConfig,
    SweepRequest,
    Variations,
    evaluate_scheme,
    make_units,
    sweep,
)

cfg = ArbitrationConfig()  # wdm8 @ 200 GHz, Table I defaults
units = make_units(cfg, seed=0, n_laser=40, n_ring=40)  # 1600 MC trials
trs = np.array([2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 8.96], np.float32)

# One SweepRequest per scheme: the whole TR axis is a single jitted call.
results = {
    scheme: sweep(SweepRequest(cfg=cfg, units=units, scheme=scheme,
                               axes={"tr_mean": trs}))
    for scheme in ("seq", "rs_ssm", "vtrs_ssm")
}

print(f"{'TR[nm]':>7s} {'AFP':>8s} {'CAFP seq':>9s} {'CAFP RS':>9s} {'CAFP VT':>9s}")
for i, tr in enumerate(results["seq"].axis("tr_mean")):
    print(
        f"{tr:7.2f} {float(results['seq'].data.afp[i]):8.4f} "
        f"{float(results['seq'].data.cafp[i]):9.4f} "
        f"{float(results['rs_ssm'].data.cafp[i]):9.4f} "
        f"{float(results['vtrs_ssm'].data.cafp[i]):9.4f}"
    )

# Every stage honors ``backend=``: None (default) is the core jnp path,
# except that a TPU builds search tables with the Pallas kernel; "jnp"
# routes table build, ideal scoring and the protocol engine's masked
# re-search through the kernel wrappers' jnp mirrors, "interpret"/"pallas"
# select the Pallas kernels (interpreter / real accelerator).  The value
# reaches every registered scheme arbiter (see the ROADMAP backend
# matrix), and CPU-reachable backends are bit-identical by contract.
res_jnp = sweep(SweepRequest(cfg=cfg, units=units, scheme="vtrs_ssm",
                             axes={"tr_mean": trs}, backend="jnp"))
assert np.array_equal(np.asarray(res_jnp.data.cafp),
                      np.asarray(results["vtrs_ssm"].data.cafp))
print("\nbackend='jnp' sweep is bit-identical to the core path")

# Point evaluations take the same Variations pytree; any registered axis
# (including post-paper ones like thermal_drift) is a valid override.
r = evaluate_scheme(
    cfg, units, "vtrs_ssm",
    variations=Variations(tr_mean=5.0, sigma_rlv=2.24, thermal_drift=0.3),
)
print(f"\npoint eval @ TR=5nm, 0.3nm thermal drift: CAFP = {float(r.cafp):.4f}")

# Protocol-engine schemes (repro.core.protocol — multi-hop augmenting LtA,
# the paper's §V-E future work) are ordinary registry entries, so a whole
# protocol-family comparison is just more SweepRequests.  protocol_lta_h1
# caps displacement chains at one hop; protocol_lta runs full multi-hop
# augmenting and tracks the *ideal* perfect-matching LtA arbiter (CAFP ~ 0).
# (Smaller Monte-Carlo batch: the round-driven simulation is heavier than
# the one-shot schemes, and the contrast shows at 256 trials already.)
units_p = make_units(cfg, seed=0, n_laser=16, n_ring=16)
protocol = {
    scheme: sweep(SweepRequest(cfg=cfg, units=units_p, scheme=scheme,
                               axes={"tr_mean": trs}, chunk_size=1))
    for scheme in ("seq_retry", "protocol_lta_h1", "protocol_lta")
}
print(f"\n{'TR[nm]':>7s} {'CAFP retry':>11s} {'CAFP hop-1':>11s} {'CAFP multi':>11s}  (vs ideal LtA)")
for i, tr in enumerate(trs):
    print(
        f"{tr:7.2f} {float(protocol['seq_retry'].data.cafp[i]):11.4f} "
        f"{float(protocol['protocol_lta_h1'].data.cafp[i]):11.4f} "
        f"{float(protocol['protocol_lta'].data.cafp[i]):11.4f}"
    )

print(
    "\nVT-RS/SSM tracks the ideal wavelength-aware LtC arbiter (CAFP ~ 0)\n"
    "while sequential Lock-to-Nearest fails on most trials — paper Fig. 14.\n"
    "Multi-hop augmenting closes the oblivious-LtA gap the same way\n"
    "(beyond-paper Fig. 19; benchmarks/fig19_lta_protocol.py)."
)

# Temporal re-arbitration (beyond-paper Fig. 20): time is a simulation
# axis.  A drift Timeline (thermal ramps, comb wander, ring aging, lane
# kill/hot-swap events) scans the protocol engine step by step; with
# warm=True each step *resumes from the previous step's lock state* —
# transactional make-before-break re-locks instead of full re-init — so
# steady steps cost ~zero probes and disturbances re-lock incrementally.
from repro.configs.wdm import drift_timeline
from repro.core import run_timeline, slice_timeline

tcfg, tl = drift_timeline("wdm16-hotswap")   # thermal ramp + lane kill/swap
tl = slice_timeline(tl, 0, 4)
units_t = make_units(tcfg, seed=1, n_laser=8, n_ring=8)
var_t = {"tr_mean": 4.0 * tcfg.grid.grid_spacing}
_, warm = run_timeline(tcfg, units_t, tl, var_t, warm=True)
_, cold = run_timeline(tcfg, units_t, tl, var_t, warm=False)
print(f"\n{'step':>4s} {'warm probes':>12s} {'cold probes':>12s} {'locked':>7s}")
for s in range(4):
    print(
        f"{s:4d} {float(np.mean(warm.probes[s])):12.1f} "
        f"{float(np.mean(cold.probes[s])):12.1f} "
        f"{float(np.mean(warm.locked[s])):7.2f}"
    )
print(
    "incremental re-lock pays a fraction of a cold start after step 0\n"
    "(benchmarks/fig20_temporal_relock.py sweeps every drift scenario)"
)

# Fabric-scale arbitration (beyond-paper Fig. 21): a whole multi-pod DWDM
# fabric — pods, link bundles, shared comb groups, routes — brought up in
# one jitted, link-chunked call, then scored against the network-level
# wavelength-assignment constraints (endpoint-matched spectral orderings,
# comb-coupled laser draws, per-route wavelength continuity).
from repro.configs.fabric import FABRIC_TINY
from repro.fabric import bringup

fres = bringup(cfg, FABRIC_TINY, tr_mean=4.6, scheme="vtrs_ssm", seed=0)
st = fres.stats
print(
    f"\nfabric bring-up ({FABRIC_TINY.n_links} links, "
    f"{FABRIC_TINY.pods} pods): link yield {float(st.link_up):.2f}, "
    f"CAFP {float(st.cafp):.4f}, matched orderings {float(st.matched):.2f},"
    f"\n  bandwidth {float(st.bandwidth):.2f}, route continuity "
    f"{float(st.route_cont):.2f}"
)

# Degraded-link report + warm repair: the interconnect runtime wraps the
# fabric layer and carries live lock state, so re-arbitration warm-restarts
# the protocol engine (transactional, monotone) instead of re-drawing.
from repro.optics.interconnect import bringup as fabric_bringup_rt
from repro.optics.interconnect import rearbitrate

fab = fabric_bringup_rt(2, 8, cfg, tr_mean=4.6, scheme="vtrs_ssm", seed=0)
for link in fab.degraded_links():
    print(
        f"  degraded link pod{link.src_pod}->pod{link.dst_pod}"
        f"#{link.transceiver}: {link.lanes_up}/{link.lanes_total} lanes "
        f"({link.failure})"
    )
fab2, rounds = rearbitrate(fab, cfg)
print(
    f"warm re-arbitration: bandwidth {fab.bandwidth_fraction:.2f} -> "
    f"{fab2.bandwidth_fraction:.2f} in {rounds} protocol round(s)\n"
    f"(sigma x TR grids over whole fabrics: SweepRequest(fabric=...); "
    f"benchmarks/fig21_fabric_yield.py runs 1008 links per point)"
)

# Fabric chaos (beyond-paper Fig. 22): the temporal and fabric axes
# compose.  A FabricTimeline carries correlated drift plus fault events —
# here a link killed at step 1 and healed at step 3 — and
# run_fabric_timeline scans every link's protocol state through it:
# disturbed links warm re-lock, undisturbed links spend nothing, and a
# link that comes back from a full outage cold-restarts its arbitration.
from repro.fabric import (
    make_fabric_timeline,
    make_fabric_units,
    run_fabric_timeline,
)

tl_f = make_fabric_timeline(
    FABRIC_TINY, 5, cfg.grid.n_ch,
    events=((1, "link_kill", 2), (3, "link_heal", 2)),
)
units_f = make_fabric_units(cfg, FABRIC_TINY, seed=0)
_, chaos = run_fabric_timeline(cfg, units_f, FABRIC_TINY, tl_f,
                               scheme="vtrs_ssm")
bw = np.asarray(chaos.fabric.bandwidth)
probes = np.asarray(chaos.probes).mean(axis=1)
print(f"\n{'step':>4s} {'bandwidth':>10s} {'mean probes':>12s}")
for s in range(tl_f.n_steps):
    print(f"{s:4d} {float(bw[s]):10.3f} {float(probes[s]):12.1f}")
print(
    "kill-and-heal: bandwidth dips while the link is down and recovers on\n"
    "heal; survivors never spend a probe (benchmarks/fig22_fabric_chaos.py\n"
    "runs comb outages, pod heating and ring death with warm-vs-cold gates)"
)

# Flight recorder (repro.obs): pass ``trace=<capacity>`` and the protocol
# engine carries a per-trial event ring (probe / lock / displace /
# surrender / release / halt) through its round loop — off by default, and
# the disabled path is bit-identical to the untraced engine.  Here we take
# a TR point where depth-1 seq_retry still fails against a feasible ideal,
# replay it through the traced engine, and let the failure taxonomy say
# *why* each residual trial failed — starvation vs displacement-storm vs
# livelock vs hopeless — from the trace alone.
from repro.core.protocol import default_rounds, run_protocol
from repro.core.relation import chain_spec
from repro.core.sampling import instantiate
from repro.core.search_table import build_search_tables
from repro.obs import format_events, trace_events
from repro.obs.taxonomy import explain_residuals

mid_tr = float(trs[len(trs) // 2])  # mid-sweep: where seq_retry leaves CAFP
tax = explain_residuals(cfg, units_p, [mid_tr], scheme="seq_retry",
                        depth=1, trace_cap=64)
print(
    f"\nflight recorder @ TR={mid_tr:.2f}nm: seq_retry loses "
    f"{tax['residual_total']} trials the ideal LtA arbiter wins;\n"
    f"taxonomy: {tax['histogram']} (unknown={tax['unknown']})"
)
if tax["points"][0]["trial_index"]:
    # replay the first failing trial with tracing on and show its events
    trial = tax["points"][0]["trial_index"][0]
    sys_q = instantiate(cfg, units_p)
    tbl = build_search_tables(sys_q, mid_tr, max_alias=cfg.max_fsr_alias)
    _, buf = run_protocol(tbl, chain_spec(cfg.s), depth=1,
                          n_rounds=default_rounds(cfg.grid.n_ch), trace=64)
    print(f"trial {trial}'s last protocol events:")
    print(format_events(trace_events(buf, trial), limit=6))
print(
    "(benchmarks/fig19_lta_protocol.py classifies every WDM16 residual;\n"
    "`python -m repro.obs.report` renders bench-run manifests from .obs/)"
)
