"""Property-testing entry point: tests import ``given / settings /
strategies`` from here instead of from ``hypothesis`` directly.

Importing this module loads and selects the repo's hypothesis profile,
once: no per-example deadline, because the first example of a property
test pays for JAX tracing and compilation, which takes far longer than
hypothesis's default 200 ms.
"""
from __future__ import annotations

from hypothesis import given, settings, strategies  # noqa: F401

settings.register_profile("repro", deadline=None)
settings.load_profile("repro")
