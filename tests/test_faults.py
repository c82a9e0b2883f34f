"""Storage fault injection, adaptive degradation, deadline-aware serving.

The headline invariants (ISSUE 8):

  * faults disabled (default) ⇒ greedy tokens AND ``io_summary()`` are
    byte/bit-identical to an engine without the fault machinery, across
    backends and wbits;
  * faults enabled ⇒ tokens are UNCHANGED (time-only perturbation), and a
    given (profile, fault_seed) replays bit-identically;
  * under a sustained thermal throttle with per-request deadlines the
    DegradationController strictly improves SLO attainment and p99 over
    the controller-off baseline, and the degraded baseline exhibits the
    preempt-and-requeue path.

The nightly ``slow`` tier adds a seeded fault-trajectory sweep across
every profile × several seeds × both backends/wbits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.faults import (
    FAULT_PROFILES,
    FaultModel,
    FaultProfile,
    ThermalTrajectory,
    get_fault_profile,
)
from repro.core.offload import FlashOffloadSimulator
from repro.models import build_model
from repro.serving import (
    DegradationController,
    Request,
    Scheduler,
    ServeEngine,
    set_plan_budget_scale,
)

slow = pytest.mark.slow

# Aggressive deterministic profile for the perturbation tests: the throttle
# engages immediately (onset 0, ~instant ramp), so every event past the
# first microsecond of device time is charged at 2x regardless of how few
# events a short decode emits or which probabilistic draws land.
HAMMER = FaultProfile(
    "hammer", spike_prob=0.3, spike_scale=4.0, fail_prob=0.2, max_retries=3,
    throttle=ThermalTrajectory(onset_s=0.0, ramp_s=1e-6, floor=0.5),
)


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("method", "chunk")
    return ServeEngine(model, params, max_seq=64, batch_size=2, device="nano",
                       sparsity=0.4, seed=1, **kw)


# -- ThermalTrajectory -------------------------------------------------------


def test_thermal_trajectory_sustained_shape():
    tr = ThermalTrajectory(onset_s=2e-3, ramp_s=10e-3, floor=0.25)
    assert tr.scale(0.0) == 1.0
    assert tr.scale(2e-3) == 1.0  # onset boundary still full speed
    mid = tr.scale(7e-3)  # halfway down the ramp
    assert 0.25 < mid < 1.0
    assert mid == pytest.approx(1.0 - 0.75 * 0.5)
    assert tr.scale(12e-3) == pytest.approx(0.25)
    assert tr.scale(1.0) == pytest.approx(0.25)  # sustained: never recovers


def test_thermal_trajectory_cycle_recovers():
    tr = ThermalTrajectory(onset_s=0.0, ramp_s=10e-3, floor=0.4, period_s=40e-3)
    low = tr.scale(15e-3)  # fully ramped within the first half
    assert low == pytest.approx(0.4)
    # second half recovers linearly back toward full speed
    assert tr.scale(30e-3) == pytest.approx(0.7)
    assert tr.scale(39.9e-3) > 0.99
    # and the pattern repeats next period
    assert tr.scale(55e-3) == pytest.approx(tr.scale(15e-3))


def test_thermal_trajectory_validation():
    with pytest.raises(ValueError, match="floor"):
        ThermalTrajectory(floor=0.0)
    with pytest.raises(ValueError, match="floor"):
        ThermalTrajectory(floor=1.5)
    with pytest.raises(ValueError):
        ThermalTrajectory(onset_s=-1.0)


# -- FaultProfile / FaultModel ----------------------------------------------


def test_fault_profile_validation():
    with pytest.raises(ValueError, match="spike_prob"):
        FaultProfile("bad", spike_prob=1.0)
    with pytest.raises(ValueError, match="spike_scale"):
        FaultProfile("bad", spike_scale=0.5)
    with pytest.raises(ValueError, match="fail_prob"):
        FaultProfile("bad", fail_prob=-0.1)
    with pytest.raises(ValueError, match="max_retries"):
        FaultProfile("bad", max_retries=-1)
    with pytest.raises(KeyError, match="unknown fault profile"):
        get_fault_profile("nope")


def test_fault_model_none_is_inert():
    fm = FaultModel("none", seed=0)
    assert not fm.enabled
    out = fm.perturb(1e-3, 0.0)
    assert out.charged_s == 1e-3 and out.retries == 0 and not out.spiked


def test_fault_model_deterministic_replay():
    a = FaultModel("degraded_nvme", seed=11)
    b = FaultModel("degraded_nvme", seed=11)
    busy_a = busy_b = 0.0
    for _ in range(200):
        oa = a.perturb(1e-4, busy_a)
        ob = b.perturb(1e-4, busy_b)
        assert oa.charged_s == ob.charged_s
        busy_a += oa.charged_s
        busy_b += ob.charged_s
    assert a.summary() == b.summary()
    # a different seed produces a different trajectory
    c = FaultModel("degraded_nvme", seed=12)
    for i in range(200):
        c.perturb(1e-4, i * 1e-4)
    assert c.summary() != a.summary()


def test_fault_model_retry_accounting_exact():
    """Transient failures: charged = (retries+1) × read + geometric
    backoff, exactly — the retry ledger must balance to the event charge."""
    p = FaultProfile("retry_only", fail_prob=0.5, max_retries=5,
                     backoff_base_s=1e-4, backoff_mult=2.0)
    fm = FaultModel(p, seed=3)
    saw_retry = False
    for _ in range(100):
        out = fm.perturb(1e-3, 0.0)
        assert out.charged_s == pytest.approx(
            1e-3 * (out.retries + 1) + out.backoff_s
        )
        if out.retries:
            saw_retry = True
            assert out.backoff_s == pytest.approx(
                1e-4 * (2.0 ** out.retries - 1)  # Σ base·mult^k, k<retries
            )
    assert saw_retry
    assert fm.summary()["retries"] > 0


def test_fault_model_spike_multiplies():
    p = FaultProfile("spiky", spike_prob=0.3, spike_scale=6.0)
    fm = FaultModel(p, seed=0)
    outs = [fm.perturb(1e-3, 0.0) for _ in range(100)]
    spiked = [o for o in outs if o.spiked]
    clean = [o for o in outs if not o.spiked]
    assert spiked and clean
    assert all(o.charged_s == pytest.approx(6e-3) for o in spiked)
    assert all(o.charged_s == pytest.approx(1e-3) for o in clean)


def test_fault_model_throttle_divides_latency():
    fm = FaultModel("thermal_throttle", seed=0)
    # before onset: clean; deep past the ramp: clean / floor
    assert fm.perturb(1e-4, 0.0).charged_s == pytest.approx(1e-4)
    assert fm.perturb(1e-4, 1.0).charged_s == pytest.approx(1e-4 / 0.25)
    assert fm.summary()["min_throttle_scale"] == pytest.approx(0.25)


def test_fault_profiles_registry():
    assert set(FAULT_PROFILES) >= {
        "none", "tail_spikes", "flaky_reads", "thermal_throttle",
        "thermal_cycle", "degraded_nvme",
    }
    assert not FAULT_PROFILES["none"].spike_prob


def test_fault_model_retries_reprice_at_advanced_clock():
    """Regression (PR 9 satellite): each retry's read must be priced at
    the throttle scale of the ADVANCED busy clock — first-attempt read +
    backoffs heat the device — not at the scale frozen from attempt 0.
    With a steep ramp the three reads land at three different scales; the
    frozen-scale bug would charge 3 × clean + backoff."""
    tr = ThermalTrajectory(onset_s=0.0, ramp_s=2e-3, floor=0.25)
    p = FaultProfile("steep", fail_prob=0.999999, max_retries=2,
                     backoff_base_s=1e-6, backoff_mult=1.0, throttle=tr)
    out = FaultModel(p, seed=0).perturb(1e-3, 0.0)
    assert out.retries == 2
    # hand-walk the clock: read0 at scale(0)=1.0, each retry re-reads at
    # the trajectory's scale of everything charged before it
    expect = 1e-3
    for _ in range(2):
        expect += 1e-6
        expect += 1e-3 / tr.scale(expect)
    assert out.charged_s == pytest.approx(expect)
    # strictly above what the frozen first-attempt scale would charge
    assert out.charged_s > 3e-3 + out.backoff_s + 1e-4
    # first attempt ran unthrottled; the outcome records attempt-0's scale
    assert out.throttle_scale == 1.0


# -- simulator measurement boundary ------------------------------------------


def test_simulator_fault_off_log_identical():
    """Attaching an inert FaultModel must not shift the simulator's RNG
    stream or event log in any way."""
    a = FlashOffloadSimulator("nano", seed=5)
    b = FlashOffloadSimulator("nano", seed=5, faults=FaultModel("none", seed=9))
    est = np.array([1e-4, 0.0, 3e-4, 2e-4])
    la = a.measure_from_estimate_batch(est, name="x")
    lb = b.measure_from_estimate_batch(est, name="x")
    np.testing.assert_array_equal(la, lb)
    assert a.log == b.log
    assert a.measure_from_estimate(1e-4) == b.measure_from_estimate(1e-4)


def test_simulator_faults_charge_time_only():
    """Faults only inflate latency; estimates, byte accounting and the
    zero-estimate steps are untouched."""
    clean = FlashOffloadSimulator("nano", seed=5, noise=0.0)
    faulty = FlashOffloadSimulator(
        "nano", seed=5, noise=0.0,
        faults=FaultModel("thermal_throttle", seed=0),
    )
    est = np.full(64, 1e-3)
    lc = clean.measure_from_estimate_batch(est, name="d", nbytes=est * 1e6)
    lf = faulty.measure_from_estimate_batch(est, name="d", nbytes=est * 1e6)
    assert lf.sum() > lc.sum()
    assert np.all(lf >= lc - 1e-15)
    assert faulty.total_bytes() == clean.total_bytes()
    # the event log records where the extra time came from
    assert sum(e.fault_s for e in faulty.log) == pytest.approx(
        float(lf.sum() - lc.sum())
    )
    assert all(e.fault_s == 0.0 for e in clean.log)


# -- DegradationController ---------------------------------------------------


def test_controller_clean_device_stays_at_full_budget():
    c = DegradationController()
    for _ in range(50):
        c.observe(np.full(8, 1.0))
    assert c.scale == 1.0 and not c.degraded
    assert c.summary()["tighten_steps"] == 0


def test_controller_tightens_then_recovers():
    c = DegradationController()
    c.observe(np.full(16, 4.0))  # sustained throttle
    assert c.scale < 1.0 and c.degraded
    tightened = c.scale
    c.observe(np.full(16, 4.0))
    assert c.scale <= tightened
    assert c.scale >= c.min_scale
    # device recovers → scale walks back to 1.0
    for _ in range(10):
        c.observe(np.full(16, 1.0))
    assert c.scale == 1.0 and not c.degraded
    s = c.summary()
    assert s["tighten_steps"] > 0 and s["relax_steps"] > 0


def test_controller_ignores_non_finite_and_validates():
    c = DegradationController()
    c.observe([np.nan, np.inf, 0.0, -1.0])
    assert c.observations == 0 and c.scale == 1.0
    with pytest.raises(ValueError, match="hysteresis"):
        DegradationController(degrade_ratio=1.0, recover_ratio=1.2)
    with pytest.raises(ValueError, match="alpha"):
        DegradationController(alpha=0.0)


def test_controller_hysteresis_never_oscillates_between_thresholds():
    """A ratio held anywhere in (recover_ratio, degrade_ratio) moves the
    scale in NEITHER direction — from healthy it never tightens, from
    degraded it never relaxes. The dead band is what keeps a borderline
    device from flapping budgets every call."""
    c = DegradationController()  # recover 1.25 < 1.4 < degrade 1.6
    for _ in range(100):
        c.observe([1.4])
    assert c.scale == 1.0
    assert c.summary()["tighten_steps"] == 0
    c.observe(np.full(32, 4.0))  # force a degrade
    assert c.scale < 1.0
    # let the EWMA decay into the dead band (it converges to 1.4 — while it
    # is still above degrade_ratio the controller keeps tightening, which
    # is correct: the dead band is a property of the FILTERED signal)
    while c.ewma >= c.degrade_ratio:
        c.observe([1.4])
    held = c.scale
    assert held < 1.0
    for _ in range(100):
        c.observe([1.4])
    assert c.scale == held  # parked: no relax, no further tighten
    assert c.summary()["relax_steps"] == 0


def test_controller_monotone_tightening_clamps_at_floor():
    """Under a sustained 4× ratio the scale walks DOWN monotonically in
    exact ``step`` decrements, clamps at min_scale, and tighten_steps
    counts only real moves (not the saturated observations)."""
    c = DegradationController()
    seen = [c.scale]
    for _ in range(20):
        c.observe([4.0])
        seen.append(c.scale)
    assert all(b <= a for a, b in zip(seen, seen[1:]))
    assert seen[-1] == c.min_scale
    moves = [a - b for a, b in zip(seen, seen[1:]) if a != b]
    assert all(m == pytest.approx(c.step) for m in moves[:-1])
    assert c.summary()["tighten_steps"] == len(moves)
    # parked at the floor: more bad observations change nothing
    c.observe(np.full(16, 4.0))
    assert c.scale == c.min_scale


def test_controller_recovery_lands_exactly_on_one():
    """Relaxation must terminate at exactly 1.0 even when min_scale is not
    step-aligned (0.5 with step 0.2 walks 0.7 → 0.9 → 1.0, the last move
    a truncated half-step) — a 0.9999… scale would silently shave every
    future budget."""
    c = DegradationController(min_scale=0.5)
    while c.scale > c.min_scale:
        c.observe([4.0])
    assert c.scale == 0.5
    seen = []
    for _ in range(20):
        c.observe([1.0])
        seen.append(c.scale)
    assert seen[-1] == 1.0  # exact, not approx
    lifts = [b - a for a, b in zip([0.5] + seen, seen) if b != a]
    assert lifts == pytest.approx([0.2, 0.2, 0.1])
    assert not c.degraded


def test_controller_random_streams_keep_invariants():
    """Deterministic sweep over seeded random ratio streams (NaN/inf/zero
    spiked in): the scale stays inside [min_scale, 1.0], only moves in
    ≤ step increments, and non-finite entries never count as
    observations."""
    for seed in range(8):
        rng = np.random.default_rng(seed)
        c = DegradationController()
        finite_seen = 0
        prev = c.scale
        for _ in range(60):
            r = rng.gamma(2.0, rng.choice([0.4, 1.2]), size=6)
            r[rng.integers(0, 6)] = rng.choice([np.nan, np.inf, 0.0, -2.0])
            finite_seen += int(np.sum(np.isfinite(r) & (r > 0)))
            c.observe(r)
            assert c.min_scale <= c.scale <= 1.0
            assert abs(c.scale - prev) <= c.step + 1e-12
            assert c.degraded == (c.scale < 1.0)
            prev = c.scale
        assert c.observations == finite_seen


def test_controller_observe_corruption_maps_rate_to_ratio():
    """The second degrade signal: rate 0 observes the healthy 1.0 (inert),
    a sustained rate above (degrade_ratio-1)/gain tightens, and non-finite
    or negative rates are ignored entirely."""
    c = DegradationController()  # gain 20: rate 0.05 → ratio 2.0 > 1.6
    for _ in range(50):
        c.observe_corruption(0.0)
    assert c.scale == 1.0 and c.observations == 50
    before = c.observations
    c.observe_corruption(np.nan)
    c.observe_corruption(-0.1)
    assert c.observations == before and c.scale == 1.0
    for _ in range(10):
        c.observe_corruption(0.05)
    assert c.scale < 1.0
    with pytest.raises(ValueError, match="corruption_ratio_gain"):
        DegradationController(corruption_ratio_gain=-1.0)
    # gain 0 turns the signal off no matter how corrupt the device is
    c0 = DegradationController(corruption_ratio_gain=0.0)
    for _ in range(20):
        c0.observe_corruption(0.5)
    assert c0.scale == 1.0


@given(st.integers(0, 2**31 - 1), st.floats(1.61, 64.0), st.integers(1, 40))
@settings(max_examples=25, deadline=None)
def test_controller_bounds_property(seed, ratio, steps):
    """Property: any sustained ratio above the degrade threshold drives the
    scale monotonically toward (never past) min_scale; a subsequent healthy
    stream always returns it to exactly 1.0."""
    rng = np.random.default_rng(seed)
    c = DegradationController()
    prev = 1.0
    for _ in range(steps):
        c.observe(np.full(rng.integers(1, 8), ratio))
        assert c.min_scale <= c.scale <= prev
        prev = c.scale
    for _ in range(40):
        c.observe(np.full(4, 1.0))
    assert c.scale == 1.0


def test_set_plan_budget_scale_validates():
    plan = {"site": {"bscale": jnp.ones((3,), jnp.float32)}}
    out = set_plan_budget_scale(plan, 0.5)
    np.testing.assert_allclose(np.asarray(out["site"]["bscale"]), 0.5)
    with pytest.raises(ValueError, match="scale"):
        set_plan_budget_scale(plan, 0.0)
    # plans without the leaf pass through untouched
    p2 = {"site": {"mask": jnp.zeros((3,))}}
    assert set_plan_budget_scale(p2, 0.5) is p2


# -- engine: the headline byte-identity invariants ---------------------------


@pytest.mark.parametrize("backend,wbits", [("reference", 16), ("kernel", 8)])
def test_engine_fault_off_byte_identity(lm, backend, wbits):
    """Fault machinery off (default) ⇒ tokens AND io_summary bit-identical
    to an engine constructed without any fault/degrade arguments."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    base = _engine(model, params, backend=backend, wbits=wbits)
    t_base = base.decode(tok0, 5)
    off = _engine(model, params, backend=backend, wbits=wbits,
                  fault_profile="none", fault_seed=123)
    t_off = off.decode(tok0, 5)
    np.testing.assert_array_equal(np.asarray(t_base), np.asarray(t_off))
    assert base.io_summary() == off.io_summary()
    fs = off.fault_summary()
    assert not fs["fault_enabled"] and fs["fault_events"] == 0


@pytest.mark.parametrize("backend,wbits", [("reference", 16), ("kernel", 8)])
def test_engine_faults_perturb_time_never_tokens(lm, backend, wbits):
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    base = _engine(model, params, backend=backend, wbits=wbits)
    t_base = base.decode(tok0, 5)
    s_base = base.io_summary()
    faulty = _engine(model, params, backend=backend, wbits=wbits,
                     fault_profile=HAMMER, fault_seed=3)
    t_faulty = faulty.decode(tok0, 5)
    s_faulty = faulty.io_summary()
    np.testing.assert_array_equal(np.asarray(t_base), np.asarray(t_faulty))
    assert s_faulty["io_est_s"] == s_base["io_est_s"]  # planning unchanged
    assert s_faulty["io_bytes"] == s_base["io_bytes"]
    assert s_faulty["io_sim_s"] > s_base["io_sim_s"]  # only time moved
    assert faulty.fault_summary()["fault_events"] > 0


def test_engine_fault_seed_deterministic(lm):
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    runs = []
    for _ in range(2):
        e = _engine(model, params, fault_profile=HAMMER, fault_seed=3)
        e.decode(tok0, 5)
        runs.append((e.io_summary(), e.fault_summary()))
    assert runs[0] == runs[1]
    other = _engine(model, params, fault_profile=HAMMER, fault_seed=4)
    other.decode(tok0, 5)
    assert other.io_summary()["io_sim_s"] != runs[0][0]["io_sim_s"]


def test_engine_degrade_clean_device_identity(lm):
    """Controller on + healthy device: the scale never leaves 1.0 and the
    whole run (tokens, io_summary) is bit-identical to degrade-off — the
    bscale plan leaf at 1.0 reproduces the static budgets exactly."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    base = _engine(model, params)
    t_base = base.decode(tok0, 6)
    on = _engine(model, params, degrade=True)
    t_on = on.decode(tok0, 6)
    np.testing.assert_array_equal(np.asarray(t_base), np.asarray(t_on))
    assert base.io_summary() == on.io_summary()
    assert on.fault_summary()["degrade_scale"] == 1.0


def test_engine_degrade_tightens_under_throttle_and_cuts_io(lm):
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)

    def run(degrade):
        e = _engine(model, params, fault_profile="thermal_throttle",
                    fault_seed=0, degrade=degrade)
        e.simulator.noise = 0.0
        for _ in range(6):
            e.decode(tok0, 4)
        return e

    off = run(False)
    on = run(True)
    fs = on.fault_summary()
    assert fs["degrade_scale"] < 1.0
    assert fs["degrade_tighten_steps"] >= 1
    # tightened budgets stream fewer bytes and charge less simulated I/O
    assert on.io_summary()["io_bytes"] < off.io_summary()["io_bytes"]
    assert on.io_summary()["io_sim_s"] < off.io_summary()["io_sim_s"]


def test_engine_degrade_needs_selecting_method(lm):
    cfg, model, params = lm
    with pytest.raises(ValueError, match="degrade"):
        _engine(model, params, method="dense", degrade=True)


def test_engine_degrade_per_token_path_applies_scale(lm):
    """The per-token loop shares the call-boundary contract: after enough
    degraded calls its controller tightens too, and the plan carries the
    scale into the next call."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    e = _engine(model, params, fault_profile="thermal_throttle",
                fault_seed=0, degrade=True)
    e.simulator.noise = 0.0
    for _ in range(6):
        e.decode_per_token(tok0, 4)
    assert e.fault_summary()["degrade_scale"] < 1.0


# -- end to end: deadlines + preemption under sustained throttle -------------


def _deadline_requests(cfg, n, max_new=6, deadline=0.03, gap=0.002):
    rng = np.random.default_rng(0)
    out = []
    for rid in range(n):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
        out.append(Request(rid=rid, prompt={"tokens": toks},
                           max_new_tokens=max_new, arrival_s=gap * rid,
                           deadline_s=deadline))
    return out


def _run_throttled(cfg, model, params, degrade):
    eng = _engine(model, params, fault_profile="thermal_throttle",
                  fault_seed=0, degrade=degrade)
    eng.simulator.noise = 0.0  # fully deterministic under --fault-seed
    sched = Scheduler(eng, round_tokens=2)
    sched.submit(_deadline_requests(cfg, 8))
    return sched.run(), eng


def test_controller_on_beats_off_on_slo(lm):
    """The acceptance scenario: sustained thermal throttle, per-request
    deadlines. Controller ON yields strictly higher attainment and
    strictly lower p99; the degraded baseline blows deadlines and
    exercises the preempt-and-requeue path (the preempted request is
    requeued, readmitted and still finishes — the run drains)."""
    cfg, model, params = lm
    off, _ = _run_throttled(cfg, model, params, degrade=False)
    on, eng_on = _run_throttled(cfg, model, params, degrade=True)
    assert off.finished == on.finished == 8  # both drained completely
    assert on.slo_attainment > off.slo_attainment
    assert on.latency_p99_s < off.latency_p99_s
    assert off.preempted >= 1  # the degraded baseline preempts + requeues
    assert eng_on.fault_summary()["degrade_scale"] < 1.0
    # deterministic: same seeds replay the exact same stats
    off2, _ = _run_throttled(cfg, model, params, degrade=False)
    assert off2 == off


def test_preempted_run_replays_token_identical(lm):
    """Evict-and-requeue restarts generation from the prompt; under a fixed
    fault seed the whole preempting run — including every restarted
    request's final tokens — replays bit-identically, and every preempted
    request still delivers its full output."""
    cfg, model, params = lm

    def run_once():
        eng = _engine(model, params, fault_profile="thermal_throttle",
                      fault_seed=0)
        eng.simulator.noise = 0.0
        sched = Scheduler(eng, round_tokens=2)
        reqs = _deadline_requests(cfg, 8)
        sched.submit(reqs)
        sched.run()
        return reqs

    a = run_once()
    pre = [r for r in a if r.preemptions > 0]
    assert pre, "scenario must exercise preemption"
    assert all(len(r.tokens_out) == r.max_new_tokens for r in a)
    b = run_once()
    for ra, rb in zip(a, b):
        assert ra.tokens_out == rb.tokens_out
        assert ra.preemptions == rb.preemptions


# -- nightly seeded fault-trajectory sweep -----------------------------------


@slow
@pytest.mark.parametrize("profile", sorted(set(FAULT_PROFILES) - {"none"}))
def test_fault_trajectory_sweep(lm, profile):
    """Nightly tier: every fault profile × several seeds × both backends
    and wbits — tokens never change, time never shrinks, replay is exact."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    for backend, wbits in (("reference", 16), ("kernel", 16),
                           ("reference", 8), ("kernel", 8)):
        # tokens are only identical at FIXED wbits (int8 storage changes
        # values by design) — baseline each (backend, wbits) combo
        base = _engine(model, params, backend=backend, wbits=wbits)
        t_base = np.asarray(base.decode(tok0, 6))
        base_sim = base.io_summary()["io_sim_s"]
        for seed in (0, 1, 2):
            e = _engine(model, params, backend=backend, wbits=wbits,
                        fault_profile=profile, fault_seed=seed)
            t = np.asarray(e.decode(tok0, 6))
            np.testing.assert_array_equal(t_base, t)
            # faults can only add charged time, never remove it
            assert e.io_summary()["io_sim_s"] >= base_sim - 1e-12
            # exact replay
            e2 = _engine(model, params, backend=backend, wbits=wbits,
                         fault_profile=profile, fault_seed=seed)
            e2.decode(tok0, 6)
            assert e2.io_summary() == e.io_summary()
            assert e2.fault_summary() == e.fault_summary()
