"""Fault-injection layer unit tests (utils/faults.py).

The injector is the foundation the whole chaos suite stands on, so its
trigger semantics (once / nth / every / probability), action semantics
(drop / error / delay), config surfaces (env string and config mapping),
and — critically — its unconfigured no-op cost are pinned here.
"""

import asyncio
import time

import pytest

from symmetry_tpu.utils.faults import (
    FAULTS,
    FaultInjector,
    InjectedFault,
    parse_rule,
)


@pytest.fixture(autouse=True)
def clean_global_faults():
    """The module-global injector must never leak rules across tests."""
    FAULTS.clear()
    yield
    FAULTS.clear()


class TestParsing:
    def test_actions(self):
        assert parse_rule("a.b", "crash").kind == "crash"
        r = parse_rule("a.b", "hang(30)")
        assert (r.kind, r.seconds) == ("hang", 30.0)
        assert parse_rule("a.b", "hang").seconds == 3600.0  # default wedge
        r = parse_rule("a.b", "delay(0.25)")
        assert (r.kind, r.seconds) == ("delay", 0.25)
        r = parse_rule("a.b", "error(boom town)")
        assert (r.kind, r.message) == ("error", "boom town")
        assert parse_rule("a.b", "drop_frame").kind == "drop_frame"

    def test_triggers(self):
        assert parse_rule("s", "crash").trigger == "always"
        assert parse_rule("s", "crash@once").trigger == "once"
        r = parse_rule("s", "crash@nth=7")
        assert (r.trigger, r.n) == ("nth", 7)
        r = parse_rule("s", "drop_frame@every=3")
        assert (r.trigger, r.n) == ("every", 3)
        r = parse_rule("s", "error@p=0.25")
        assert (r.trigger, r.prob) == ("p", 0.25)

    def test_invalid_specs_fail_loudly(self):
        for bad in ("explode", "crash@sometimes", "delay", "crash(5)",
                    "drop_frame@nth=0"):
            with pytest.raises(ValueError):
                parse_rule("s", bad)
        inj = FaultInjector()
        with pytest.raises(ValueError):
            inj.load("no-equals-sign")
        with pytest.raises(ValueError):
            inj.load(42)
        assert not inj.enabled  # a rejected load arms nothing

    def test_env_string_and_mapping_forms(self):
        inj = FaultInjector()
        inj.load("a.b=drop_frame@every=2; c.d=error(x)@once")
        inj.load({"e.f": "delay(0.01)", "g.h": ["crash@nth=9",
                                                "drop_frame@p=0.5"]})
        seams = {r.seam for r in inj.rules()}
        assert seams == {"a.b", "c.d", "e.f", "g.h"}
        assert inj.enabled
        inj.clear()
        assert not inj.enabled and not inj.rules()


class TestTriggers:
    def test_once_fires_exactly_once(self):
        inj = FaultInjector()
        inj.load("s=drop_frame@once")
        assert [inj.point("s") for _ in range(4)] == [True, False,
                                                     False, False]

    def test_nth_fires_exactly_on_the_nth_hit(self):
        inj = FaultInjector()
        inj.load("s=drop_frame@nth=3")
        assert [inj.point("s") for _ in range(5)] == [False, False, True,
                                                     False, False]

    def test_every_n(self):
        inj = FaultInjector()
        inj.load("s=drop_frame@every=2")
        assert [inj.point("s") for _ in range(6)] == [False, True] * 3

    def test_probability_bounds(self):
        inj = FaultInjector()
        inj.load("always=drop_frame@p=1.0; never=drop_frame@p=0.0")
        assert all(inj.point("always") for _ in range(8))
        assert not any(inj.point("never") for _ in range(8))

    def test_unknown_seam_never_fires(self):
        inj = FaultInjector()
        inj.load("s=drop_frame")
        assert inj.point("other.seam") is False

    def test_counters(self):
        inj = FaultInjector()
        inj.load("s=drop_frame@every=2")
        for _ in range(4):
            inj.point("s")
        assert inj.counters() == {"s": {"hits": 4, "fired": 2}}

    def test_multiple_rules_one_seam_budget_not_consumed_by_winner(self):
        """First armed rule wins a hit; later rules record the hit but
        keep their trigger budget — `fired` counts APPLIED actions only,
        which is what the chaos assertions read."""
        inj = FaultInjector()
        inj.load({"s": ["drop_frame@once", "drop_frame@every=2"]})
        # hit 1: rule A (@once) fires; rule B's budget untouched
        # hit 2: A spent; B sees its 2nd hit → every=2 fires
        # hit 3: nothing; hit 4: B fires again
        assert [inj.point("s") for _ in range(4)] == [True, True,
                                                     False, True]
        assert inj.counters() == {"s": {"hits": 8, "fired": 3}}


class TestActions:
    def test_error_raises_injected_fault(self):
        inj = FaultInjector()
        inj.load("s=error(kapow)")
        with pytest.raises(InjectedFault, match="kapow"):
            inj.point("s")

    def test_error_default_message_names_the_seam(self):
        inj = FaultInjector()
        inj.load("host.pipe_write=error")
        with pytest.raises(InjectedFault, match="host.pipe_write"):
            inj.point("host.pipe_write")

    def test_delay_blocks_then_proceeds(self):
        inj = FaultInjector()
        inj.load("s=delay(0.05)")
        t0 = time.monotonic()
        assert inj.point("s") is False
        assert time.monotonic() - t0 >= 0.04

    def test_apoint_async_delay_and_drop(self):
        inj = FaultInjector()
        inj.load("s=delay(0.05)@once; d=drop_frame")

        async def main():
            t0 = time.monotonic()
            assert await inj.apoint("s") is False
            assert time.monotonic() - t0 >= 0.04
            assert await inj.apoint("d") is True
            with pytest.raises(InjectedFault):
                inj.load("e=error")
                await inj.apoint("e")

        asyncio.new_event_loop().run_until_complete(main())


class TestNoopOverhead:
    def test_unconfigured_injector_is_a_noop(self, monkeypatch, loop_ratio):
        """The contract instrumented hot paths rely on: with nothing
        armed, a seam costs one attribute read + one early return. It
        never reaches `fire` (no lock, no hit recorded), and it costs a
        small multiple of an empty call (1.2x guarded, 2x unguarded here),
        whatever the machine's load."""
        inj = FaultInjector()
        assert inj.enabled is False
        fired = []
        monkeypatch.setattr(inj, "fire", fired.append)

        def guarded():
            if inj.enabled and inj.point("host.pipe_write"):
                pass

        assert loop_ratio(guarded, 50_000) < 25
        # and point() itself stays cheap when called without the guard
        assert loop_ratio(lambda: inj.point("host.pipe_write"), 50_000) < 25
        assert fired == [] and inj.counters() == {}

    def test_global_injector_starts_disabled_without_env(self):
        # The autouse fixture cleared it; this is the state every
        # production process without SYMMETRY_FAULTS runs in.
        assert FAULTS.enabled is False
        assert FAULTS.point("any.seam") is False
