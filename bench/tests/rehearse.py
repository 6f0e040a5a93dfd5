"""Steer a benchmark run onto the CPU at a tiny size, for the tests.

The harness has no option for this: the test replaces its device check,
leaves JAX's persistent cache off, and shrinks each configuration and
traffic mix as it is loaded by name, by the sizes its own file gives
under ``rehearsal``.  So a cell that later files add is rehearsed with
no edit here."""
from __future__ import annotations

import json


def tiny(load):
    """``harness.load_json`` with each configuration and traffic mix
    shrunk by its ``rehearsal`` sizes."""
    def small(kind, name):
        d = load(kind, name)
        if kind in ("configs", "traffic"):
            d.update(d.get("rehearsal", {}))
        return d
    return small


def steer(monkeypatch):
    import jax
    from bench import harness
    monkeypatch.setattr(harness, "load_json", tiny(harness.load_json))
    monkeypatch.setattr(harness, "require_device",
                        lambda platform, chips: jax.devices())
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def run_cell(capsys, workload: str, seed: int = 3, seconds: float = 0.5,
             trace: int = 0):
    """(exit code, the last stdout line as JSON or None, stderr)."""
    from bench import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)])
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if lines else None), err
