"""Fault tolerance: watchdog, retry."""

import pytest

from repro.distributed.fault import StepWatchdog, retry_step


def test_watchdog_flags_stragglers():
    events = []
    wd = StepWatchdog(timeout_factor=3.0, min_history=5,
                      on_straggler=lambda dt, med: events.append((dt, med)))
    for _ in range(10):
        wd.observe_for_test(0.1)
    wd.observe_for_test(0.5)      # 5× median → straggler
    assert wd.straggler_events == 1
    assert events and events[0][0] == pytest.approx(0.5)
    wd.observe_for_test(0.12)     # normal again
    assert wd.straggler_events == 1


def test_watchdog_needs_history():
    wd = StepWatchdog(min_history=5)
    wd.observe_for_test(10.0)     # first step slow (compile) — no event
    assert wd.straggler_events == 0


def test_retry_step_retries_then_raises():
    calls = []

    def flaky():
        calls.append(1)
        raise RuntimeError("transient")

    with pytest.raises(RuntimeError):
        retry_step(flaky, retries=2)
    assert len(calls) == 3

    attempts = []

    def ok_after_one():
        attempts.append(1)
        if len(attempts) < 2:
            raise RuntimeError("once")
        return "fine"

    assert retry_step(ok_after_one, retries=2) == "fine"


def test_retry_step_backoff_charges_injected_clock():
    """Backoff routes through the injectable Clock: on a SimClock the
    2^k ladder is pure simulated time — deterministic, no wall sleep —
    and a success consumes only the backoff of the failed attempts."""
    from repro.core.clock import SimClock

    clk = SimClock()
    attempts = []

    def ok_after_two():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return "fine"

    assert retry_step(ok_after_two, retries=3, backoff_s=0.5,
                      clock=clk) == "fine"
    assert clk.now() == pytest.approx(0.5 + 1.0)    # 0.5·2^0 + 0.5·2^1

    # exhaustion: no backoff after the FINAL attempt (nothing to wait for)
    clk2 = SimClock()
    with pytest.raises(RuntimeError):
        retry_step(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                   retries=2, backoff_s=0.25, clock=clk2)
    assert clk2.now() == pytest.approx(0.25 + 0.5)

    # default backoff_s=0 keeps the historical retry-immediately path
    clk3 = SimClock()
    with pytest.raises(RuntimeError):
        retry_step(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                   retries=1, clock=clk3)
    assert clk3.now() == 0.0
