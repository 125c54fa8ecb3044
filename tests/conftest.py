"""Shared test configuration: hypothesis profiles, fault-drill fixtures and
the order in which xdist hands out slow files.

* ``dev`` (default) — the tier-1 smoke depth: few examples so the full
  suite stays fast on a laptop and in the tier-1 CI job.
* ``ci`` — the deep adversarial run (`--hypothesis-profile=ci`): fixed
  derandomized seed, higher example count, no deadline.  The dedicated
  conformance CI job uses this so the dispatch conformance suite explores
  far more schedules than the smoke does.

Tests that want profile-controlled depth must NOT pin ``max_examples`` in
their own ``@settings`` (a local setting overrides the profile).

The fault-drill helpers below are thin re-exports of :mod:`repro.chaos`
(PR 9): the head-rewind / stale-advisory mechanics that used to be
duplicated inline across test_pallas_ws.py, test_steal_policy.py,
test_dispatch_conformance.py and test_wstrace.py now live on
``FaultPlan``/``RewindSpec``, and the suites import them from here
(``from conftest import ...``) or take the fixtures.  ``RewindSpec.draw``
takes the same ``draw_int``/``draw_bool`` source the check functions use,
so hypothesis and the seeded slices drive identical storm shapes —
and conformance drills can apply ONE drawn spec to several layout-parity
states.
"""

try:
    from hypothesis import HealthCheck, settings
except ImportError:  # hypothesis is an optional dev dep; bare envs skip
    pass
else:
    _COMMON = dict(
        deadline=None,  # pallas interpret launches dwarf any deadline
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )
    settings.register_profile("dev", max_examples=10, **_COMMON)
    settings.register_profile("ci", max_examples=40, derandomize=True, **_COMMON)
    settings.load_profile("dev")

import random  # noqa: E402

import pytest  # noqa: E402

try:
    from repro.chaos import (  # noqa: F401  (re-exported for the suites)
        FaultPlan,
        RewindSpec,
        apply_rewind,
        resume_state,
        seed_advisory,
    )

    HAVE_CHAOS = True
except ImportError:  # bare env without src on the path
    HAVE_CHAOS = False


def rng_draws(seed):
    """Seeded ``(draw_int, draw_bool)`` source for the deterministic slices
    of the conformance suites (the hypothesis drivers draw the same way)."""
    rng = random.Random(seed)
    return (lambda lo, hi: rng.randint(lo, hi)), (lambda: rng.random() < 0.5)


def full_rewind(state, res):
    """The classic maximal §7 drill: resume from a finished launch, then
    drag every head to 0 and wipe every local bound — every already-claimed
    slot becomes claimable exactly once more (mult == 2)."""
    resume_state(state, res)
    return apply_rewind(state, RewindSpec.full(state))


def drawn_rewind(state, res, draw_int, draw_bool, *, heads=None,
                 advisory_modes=("exact",)):
    """Resume from ``res`` and apply a drawn storm; returns the spec so a
    second (layout-parity) state can replay the identical rewind with
    ``apply_rewind``."""
    resume_state(state, res)
    spec = RewindSpec.draw(state, draw_int, draw_bool, heads=heads,
                           advisory_modes=advisory_modes)
    apply_rewind(state, spec)
    return spec


# Under ``--dist loadfile`` xdist hands files out by test count, most first,
# so a slow file with few cases would start late and set the suite's wall
# time.  The files named here start first.
SLOW_FILES = ("tests/test_mesh_conformance.py",)


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class SlowFilesFirst(LoadFileScheduling):
        def _assign_work_unit(self, node):
            for scope in reversed(SLOW_FILES):
                if scope in self.workqueue:
                    self.workqueue.move_to_end(scope, last=False)
            super()._assign_work_unit(node)

    return SlowFilesFirst(config, log)


@pytest.fixture
def fault_plan_factory():
    """Seed -> FaultPlan (the hypothesis-friendly whole-plan constructor)."""
    if not HAVE_CHAOS:
        pytest.skip("repro.chaos unavailable")
    return FaultPlan.from_seed


@pytest.fixture
def rewind_storm():
    """The full-rewind drill as a fixture: ``rewind_storm(state, res)``."""
    if not HAVE_CHAOS:
        pytest.skip("repro.chaos unavailable")
    return full_rewind
