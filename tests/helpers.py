"""Shared test helpers, imported as `from helpers import ...`."""
import pytest


def virtual_devices(n):
    """The first `n` jax devices, or skip the test cleanly.

    Sharding tests call this to run on a real multi-device topology in
    CPU-only CI (`REPRO_VIRTUAL_DEVICES=8` splits the host before jax
    boots).  Without the opt-in — or when the flag could not apply, e.g.
    jax was already initialized — the suite still collects and the
    multi-device cases skip with the recipe in the reason.
    """
    import jax
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(
            f"needs {n} devices, have {len(devs)} — run with "
            f"REPRO_VIRTUAL_DEVICES={max(n, 8)} to split the host CPU")
    return devs[:n]


def given_or_cases(argnames, cases, strategies, max_examples=100):
    """Property test when hypothesis is installed, fixed cases otherwise.

    `strategies` is a callable receiving `hypothesis.strategies` and
    returning the kwargs for `@given`; `cases` are
    `@pytest.mark.parametrize(argnames, ...)` tuples in the same order,
    used on minimal installs so the module still collects and runs.
    """
    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st
    except ImportError:
        return pytest.mark.parametrize(argnames, cases)

    def deco(fn):
        return settings(max_examples=max_examples,
                        deadline=None)(given(**strategies(st))(fn))

    return deco


def active_fetches(registry) -> int:
    """Reads of a device active mask back to the host, summed over the
    registry's engines (`engine_active_fetches_total`)."""
    fam = registry.get("engine_active_fetches_total")
    return int(sum(ch.value for _, ch in fam.series()))
