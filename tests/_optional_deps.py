"""Optional test dependencies: property-based tests skip when hypothesis is
missing instead of breaking collection of the whole module (ISSUE 1
satellite: the tier-1 suite must run without optional deps)."""
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:             # pragma: no cover - env dependent
    HAS_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def settings(*_args, **_kwargs):
        return lambda fn: fn

    def example(*_args, **_kwargs):
        return lambda fn: fn

    class _AnyStrategy:
        """Placeholder so module-level strategy expressions still evaluate."""

        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _AnyStrategy()
