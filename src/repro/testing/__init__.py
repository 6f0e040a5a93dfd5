from repro.testing.hypo import given, settings, strategies  # noqa: F401
