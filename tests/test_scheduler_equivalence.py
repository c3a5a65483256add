"""Planner equivalence: PackedRefitScheduler == RefitScheduler, exactly.

The packed planner (one fused device scoring call + PriorityBuckets pops)
promises BYTE-IDENTICAL admit/evict/release decisions to the dict-sorting
reference planner — not "usually the same", identical.  That promise is what
lets the serving default change without re-litigating six tests' worth of
admission semantics, so it gets three layers of enforcement here:

  * a seeded random sweep that always runs (no optional deps) — hundreds of
    random fleets through both planners, plans compared field by field;
  * plan invariants the server's `_apply_plan` relies on (unique slot
    assignments, released slots re-fillable within the same plan);
  * a hypothesis property test (skipped when hypothesis is not installed)
    that searches the same space adversarially.

Fleet generation keeps every priority EXACTLY representable in both float32
(device ranking) and float64 (host comparisons): min_samples a power of two,
weights in {0.5, 1, 2, 4}, divergence a multiple of 1/8, integer samples.
Cross-precision ranking swaps are then impossible, so any plan mismatch is a
real semantics bug, not a rounding coin-flip (see twin/packed.py's precision
contract for why near-ties are the one tolerated divergence in production).
"""
import random

import jax
import numpy as np
import pytest

from repro.twin.packed import (PackedFleet, _device_operands, _score_terms,
                               fleet_scores)
from repro.twin.scheduler import (PackedRefitScheduler, PriorityBuckets,
                                  RefitScheduler, SchedulerConfig, TwinRecord)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # container image ships without hypothesis
    HAVE_HYPOTHESIS = False

MIN_SAMPLES = (1, 2, 4, 8, 16)
WEIGHTS = (0.5, 1.0, 2.0, 4.0)


def _random_case(rng):
    """One random (cfg, twins, max_active) planning problem."""
    slots = rng.randint(1, 6)
    cfg = SchedulerConfig(
        slots=slots,
        min_samples=rng.choice(MIN_SAMPLES),
        staleness_weight=rng.choice(WEIGHTS),
        divergence_weight=rng.choice(WEIGHTS),
        evict_margin=rng.choice([0.0, 0.5, 1.0]),
        min_residency=rng.choice([0, 1, 2, 4]),
        max_residency=rng.choice([2, 4, 8]),
        release_divergence=rng.choice([0.05, 0.25, 1.0]))
    n = rng.randint(0, 40)
    free_slots = list(range(slots))
    rng.shuffle(free_slots)
    twins = {}
    for tid in range(n):
        resident = bool(free_slots) and rng.random() < 0.3
        rec = TwinRecord(
            twin_id=tid, ring_slot=tid,
            refit_slot=free_slots.pop() if resident else None,
            samples=rng.randint(0, 48),
            deployed=rng.random() < 0.5,
            residency=rng.randint(0, 20) if resident else 0,
            divergence=rng.randint(0, 24) / 8)
        rec.samples_at_deploy = rng.randint(0, rec.samples)
        twins[tid] = rec
    max_active = rng.choice([None, rng.randint(0, slots)])
    return cfg, twins, max_active


def _both_plans(cfg, twins, max_active):
    ref = RefitScheduler(cfg).plan(twins, max_active=max_active)
    got = PackedRefitScheduler(cfg).plan_records(twins,
                                                 max_active=max_active)
    return ref, got


def test_random_fleets_plan_identically():
    rng = random.Random(1234)
    for _ in range(400):
        cfg, twins, max_active = _random_case(rng)
        ref, got = _both_plans(cfg, twins, max_active)
        assert got.admit == ref.admit
        assert got.evict == ref.evict
        assert got.release == ref.release


def test_plans_obey_slot_invariants():
    """What `TwinServer._apply_plan` assumes: admitted slots are distinct,
    every admitted twin appears once, no admitted twin is simultaneously
    evicted/released, and evicted/released twins were residents."""
    rng = random.Random(99)
    for _ in range(200):
        cfg, twins, max_active = _random_case(rng)
        plan = PackedRefitScheduler(cfg).plan_records(twins,
                                                      max_active=max_active)
        slots_assigned = [s for s, _ in plan.admit]
        tids_admitted = [t for _, t in plan.admit]
        assert len(set(slots_assigned)) == len(slots_assigned)
        assert len(set(tids_admitted)) == len(tids_admitted)
        outgoing = set(plan.evict) | set(plan.release)
        assert not outgoing & set(tids_admitted)
        for tid in outgoing:
            assert twins[tid].refit_slot is not None
        for _, tid in plan.admit:
            assert twins[tid].refit_slot is None
        # applying the plan never double-books a slot
        occupied = {r.refit_slot for r in twins.values()
                    if r.refit_slot is not None and r.twin_id not in outgoing}
        for slot, _ in plan.admit:
            assert slot not in occupied
            occupied.add(slot)


def test_released_slot_is_readmittable_same_tick():
    """A converged resident's slot can be handed to a waiting twin within
    the SAME plan — release and admit are one turnover, not two ticks."""
    cfg = SchedulerConfig(slots=2, min_samples=10, min_residency=2,
                          max_residency=8)
    resident = TwinRecord(twin_id=0, ring_slot=0, refit_slot=0, samples=50,
                          deployed=True, samples_at_deploy=50, residency=9,
                          divergence=0.01)
    other = TwinRecord(twin_id=2, ring_slot=2, refit_slot=1, samples=50,
                       deployed=True, samples_at_deploy=50, residency=4)
    waiting = TwinRecord(twin_id=1, ring_slot=1, samples=50)
    twins = {0: resident, 1: waiting, 2: other}
    plan = PackedRefitScheduler(cfg).plan_records(twins)
    assert plan.release == [0]
    assert plan.admit == [(0, 1)]      # the freed slot, refilled this tick


def test_priority_buckets_orders_exactly():
    """Pops come out in exact (-priority, key) order across buckets, with
    lazy deletion and reprioritization honored."""
    rng = random.Random(7)
    q = PriorityBuckets(quantum=0.25)
    live = {}
    for key in range(200):
        prio = rng.randint(0, 64) / 8
        q.push(key, prio)
        live[key] = prio
    for key in rng.sample(list(live), 60):       # lazy deletions
        q.discard(key)
        del live[key]
    for key in rng.sample(list(live), 40):       # reprioritizations
        live[key] = rng.randint(0, 64) / 8
        q.push(key, live[key])
    assert len(q) == len(live)
    expect = sorted(live.items(), key=lambda kv: (-kv[1], kv[0]))
    got = []
    while len(q):
        key, prio, _ = q.pop()
        got.append((key, prio))
    assert got == expect
    assert q.pop() is None and q.peek() is None


if HAVE_HYPOTHESIS:
    @st.composite
    def _cases(draw):
        seed = draw(st.integers(min_value=0, max_value=2**32 - 1))
        return _random_case(random.Random(seed))

    @pytest.mark.hypothesis
    @settings(deadline=None, max_examples=60)
    @given(_cases())
    def test_property_plans_identical(case):
        cfg, twins, max_active = case
        ref, got = _both_plans(cfg, twins, max_active)
        assert (got.admit, got.evict, got.release) == \
            (ref.admit, ref.evict, ref.release)
else:
    @pytest.mark.hypothesis
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_plans_identical():
        pass


# --------------------------------------------------------------------- #
# the fused scoring call's packed read back
# --------------------------------------------------------------------- #
def _random_packed(rng, capacity, n_waiting_max):
    """A random PackedFleet of `capacity` rows with at most `n_waiting_max`
    ready, unslotted rows (float divergences, not only exact ones)."""
    fleet = PackedFleet(capacity)
    n = int(rng.integers(1, capacity + 1))
    fleet.registered[:n] = True
    fleet.twin_id[:n] = np.arange(n)
    fleet.samples[:n] = rng.integers(0, 64, n)
    fleet.samples_at_deploy[:n] = rng.integers(0, fleet.samples[:n] + 1)
    fleet.deployed[:n] = rng.random(n) < 0.5
    fleet.set_divergence(slice(0, n), rng.random(n) * 3.0)
    ready = np.flatnonzero(fleet.registered & (fleet.samples >= 4))
    waiting = rng.permutation(ready)[:n_waiting_max]
    fleet.resident[ready] = True
    fleet.resident[waiting] = False
    return fleet


@pytest.mark.parametrize("capacity", [64, 4096])
def test_packed_fleet_scores_bit_identical_to_unpacked(capacity):
    """`fleet_scores` reads one packed int32 array; unpacked on the host it
    must equal, bit for bit, the four results of the same jnp expressions
    evaluated unpacked: rows, priorities (the -inf padding included),
    waiting depth and pressure.  Fleets with fewer waiting rows than k
    exercise the padding."""
    unpacked = jax.jit(_score_terms, static_argnames=("k",))
    rng = np.random.default_rng(capacity)
    for _ in range(12):
        k = int(rng.choice([1, 8, 64]))
        n_waiting_max = int(rng.choice([0, 1, k // 2, k, 4 * k, capacity]))
        fleet = _random_packed(rng, capacity, n_waiting_max)
        min_samples, sw, dw = 4, float(rng.choice(WEIGHTS)), float(
            rng.random() * 4)
        rows, prio, n_waiting, pressure = fleet_scores(
            fleet, min_samples=min_samples, sw=sw, dw=dw, k=k)
        want = jax.device_get(unpacked(
            *_device_operands(fleet), np.int32(min_samples), np.float32(sw),
            np.float32(dw), k=min(k, capacity)))
        np.testing.assert_array_equal(rows, want[0])
        assert prio.dtype == np.float32
        np.testing.assert_array_equal(prio.view(np.int32),
                                      want[1].view(np.int32))
        assert n_waiting == int(want[2])
        assert np.float32(pressure).view(np.int32) == \
            np.asarray(want[3], np.float32).view(np.int32)
        n_real = min(k, n_waiting)
        assert np.isneginf(prio[n_real:]).all()
        assert np.isfinite(prio[:n_real]).all()
